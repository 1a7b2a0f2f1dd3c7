"""The correctness check on the CPU at a tiny width, with the cell's own
limits: a sound run passes, and ``correct`` comes out false for the
control (the reference one precision step down in the program's place)
and for each fault planted under the timed path — a step that leaves
the state unchanged, half of each batch left out, and an answer altered
where it is produced."""
import time

import jax
import pytest

import bench
import readings

WORKLOAD = "qwen2-1.5b-l4.t4096"
TINY_ARCH = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                 d_ff=128, vocab_size=128, num_layers=2, rotary_dims=16,
                 rope_theta=10000.0, norm_eps=1e-6, qkv_bias=True)
SEED = 4_000_000_007


@pytest.fixture(scope="module")
def cell():
    spec = bench.load_json(bench.CHECKOUT / "BENCHMARK.json")
    c = bench.resolve(spec, WORKLOAD)
    c.config["arch"] = dict(TINY_ARCH)
    c.config["program"]["overrides"] = dict(
        num_layers=2, unit_repeats=2, vocab_size=128, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128)
    c.traffic.update(batch=2, seq=32)
    return c


@pytest.fixture(scope="module")
def entry():
    return bench.load_module(bench.HERE / "entries" / "run_cluster.py")


def run_cell(entry, cell, **broken):
    program = entry.build_program(cell, **broken)
    res = entry.run(cell, SEED, 0.3, False, time.perf_counter(),
                    program=program)
    return res


def test_a_sound_run_is_correct(entry, cell):
    res = run_cell(entry, cell)
    assert res["error"] is None
    assert res["correct"], res["checks"]
    assert res["attempted"] >= res["ctx"]["grads"] > 0
    assert res["failed"] == 0


def half_batch(grad_fn):
    return lambda params, batch: grad_fn(params, batch[: batch.shape[0] // 2])


def altered_answer(grad_fn):
    def g(params, batch):
        out = grad_fn(params, batch)
        leaves, tree = jax.tree.flatten(out)
        leaves[0] = 2.0 * leaves[0]
        return jax.tree.unflatten(tree, leaves)
    return g


@pytest.mark.parametrize("fault,broken", [
    ("state_unchanged", dict(hp_overrides={"lr": 0.0})),
    ("half_batch", dict(grad_wrap=half_batch)),
    ("altered_answer", dict(grad_wrap=altered_answer)),
])
def test_a_broken_timed_path_is_not_correct(entry, cell, fault, broken):
    res = run_cell(entry, cell, **broken)
    assert not res["correct"], (fault, res["checks"])


def test_the_control_is_not_correct(entry, cell):
    params0, rows = entry.make_inputs(cell, SEED)
    ref = entry.Judge(cell, params0, rows)
    ctrl = entry.Judge(cell, params0, rows, prec="fp8",
                       state_dtype="bfloat16",
                       orders=entry.round_orders(2, 3)[:1])
    nums = ref.numbers(*ctrl.candidate())
    assert any(nums[k] > cell.limits[k] for k in ("dtheta_gap", "loss_gap"))


def test_the_readings_script_reads_every_kind(cell):
    out = readings.readings(cell, [SEED], [SEED], log=lambda *a: None)
    assert set(out) == {"program", "control", "half_batch", "altered_answer"}
    assert out["program"][SEED]["dtheta_gap"] <= cell.limits["dtheta_gap"]
