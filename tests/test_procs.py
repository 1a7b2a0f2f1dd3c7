"""Process-backend tests, plus regression tests for the three bugfixes
that ride along with it (master shutdown hang, fan-out telemetry drops,
hot-row validation bound).

The load-bearing contract mirrors the threaded backend's: under a pinned
round-robin message schedule (``pin_schedule=True``) the process backend
must reproduce the threaded backend *bit-for-bit* for elementwise
families — same worker/lag/step telemetry, same final parameters — so
the threaded runtime (itself pinned to the discrete-event engine)
remains the reference semantics across the process boundary.
"""
import dataclasses
import os
import time

import jax
import numpy as np
import pytest

from repro.cluster import ClusterConfig, run_cluster
from repro.cluster.mailbox import GradMsg, Reply, _ReplyGroup
from repro.core import GammaModel, HyperParams, make_algorithm
from repro.core.flat import FlatSpec
from repro.data.synthetic import ClassificationTask
from repro.models.toy import ClassifierGradFn, make_classifier_fns

HP = HyperParams(lr=0.05, momentum=0.9)
TASK = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
INIT, _, MAKE_EVAL = make_classifier_fns([8, 16, 4])
PARAMS0 = INIT(jax.random.PRNGKey(0))
GRAD_FN = ClassifierGradFn([8, 16, 4])
EVAL_FN = MAKE_EVAL(TASK.eval_batch(32))


def _cfg(backend, *, shards=1, grads=24, workers=2, rpc_timeout=60.0,
         **kw):
    return ClusterConfig(num_workers=workers, total_grads=grads,
                         eval_every=8, mode="free",
                         exec_model=GammaModel(seed=5), backend=backend,
                         shards=shards, rpc_timeout=rpc_timeout, **kw)


def _run(name, backend, **kw):
    stats = {}
    algo = make_algorithm(name, HP)
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                       _cfg(backend, **kw), EVAL_FN, stats_out=stats)
    return hist, stats


def _leaves(params):
    return [np.asarray(x) for x in jax.tree.leaves(params)]


# ---------------------------------------------------------------------------
# backend equivalence: pinned schedule -> threaded == process
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 2])
def test_process_backend_bitexact_pinned(shards):
    ht, st = _run("dana-zero", "thread", shards=shards, pin_schedule=True)
    hp, sp = _run("dana-zero", "process", shards=shards, pin_schedule=True)
    # schedule telemetry is identical by construction (round-robin pin)
    assert hp.worker == ht.worker
    assert hp.lag == ht.lag
    assert hp.step == ht.step
    np.testing.assert_allclose(hp.gap, ht.gap, rtol=1e-6)
    # elementwise family, same per-row message order -> bit-exact params
    for a, b in zip(_leaves(ht.final_params), _leaves(hp.final_params)):
        np.testing.assert_array_equal(a, b)
    assert hp.eval_step == ht.eval_step
    np.testing.assert_allclose(hp.eval_loss, ht.eval_loss, rtol=1e-6)
    assert sp["backend"] == "process"
    assert sp["applied"] == st["applied"] == 24
    assert sp["shard_applied"] == [24] * shards
    assert sp["telemetry_dropped"] == 0


def test_process_backend_ga_asgd_allclose():
    # gap-aware member: the momentum correction consumes the telemetry
    # norms, so cross-backend float reassociation shows up in the tail —
    # allclose, not bit-exact, is the contract here (shards=1 only; the
    # cross-shard norm exchange is threads-only)
    ht, _ = _run("ga-asgd", "thread", pin_schedule=True)
    hp, _ = _run("ga-asgd", "process", pin_schedule=True)
    assert hp.worker == ht.worker
    for a, b in zip(_leaves(ht.final_params), _leaves(hp.final_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_process_backend_free_run_completes():
    # unpinned free mode: no schedule guarantee, but conservation holds
    hist, stats = _run("dana-zero", "process", shards=2)
    assert stats["applied"] == 24
    assert sum(stats["grads_per_worker"].values()) == 24
    assert len(hist.step) == 24
    assert hist.final_params is not None
    assert stats["mean_coalesce"] >= 1.0


# ---------------------------------------------------------------------------
# fault surfacing: a killed worker process must name itself, never hang
# ---------------------------------------------------------------------------
class _KillerBatch:
    """Picklable batch source that hard-kills worker 1's process on its
    third draw — simulates an OOM-killed / crashed worker child."""

    def __init__(self, task):
        self.task = task

    def __call__(self, wid, counter):
        if wid == 1 and counter >= 2:
            os._exit(1)
        return self.task.batch(wid, counter)


def test_worker_process_death_surfaces_and_does_not_hang():
    algo = make_algorithm("dana-zero", HP)
    cfg = _cfg("process", grads=100000, workers=2, rpc_timeout=5.0)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="worker-1"):
        run_cluster(algo, GRAD_FN, PARAMS0, _KillerBatch(TASK), cfg)
    assert time.monotonic() - t0 < 60.0


# ---------------------------------------------------------------------------
# support matrix: clean errors, no processes spawned
# ---------------------------------------------------------------------------
def test_process_backend_rejects_deterministic_mode():
    algo = make_algorithm("dana-zero", HP)
    cfg = dataclasses.replace(_cfg("process"), mode="deterministic")
    with pytest.raises(ValueError, match="live modes"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)


def test_process_backend_rejects_closure_grad_fn():
    algo = make_algorithm("dana-zero", HP)
    with pytest.raises(ValueError, match="picklable grad_fn"):
        run_cluster(algo, lambda p, b: p, PARAMS0, TASK.batch,
                    _cfg("process"))


def test_process_backend_rejects_gap_aware_sharded():
    algo = make_algorithm("ga-asgd", HP)
    with pytest.raises(ValueError, match="shards=1"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                    _cfg("process", shards=2))


def test_process_backend_rejects_hot_rows():
    algo = make_algorithm("dana-zero", HP)
    rows = FlatSpec.from_tree(PARAMS0).rows
    cfg = _cfg("process", hot_rows=((0, rows), None))
    with pytest.raises(ValueError, match="hot_rows"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)


def test_process_backend_refused_on_tpu(monkeypatch):
    """Only one process may hold a TPU chip: on a TPU backend the run is
    refused before any child is spawned."""
    import multiprocessing

    def no_spawn(*a, **k):
        raise AssertionError("a child process was started")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(multiprocessing, "get_context", no_spawn)
    algo = make_algorithm("dana-zero", HP)
    with pytest.raises(ValueError, match="one process may hold a TPU"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, _cfg("process"))


# ---------------------------------------------------------------------------
# regression: master shutdown hang (unbounded join)
# ---------------------------------------------------------------------------
def test_stuck_master_serve_loop_surfaces_instead_of_hanging(monkeypatch):
    from repro.cluster import master as master_mod

    def stuck_serve(self):
        # a wedged serve loop: signals stop (so workers drain out and the
        # old unbounded join would wait forever) but never returns
        self.stop.set()
        time.sleep(30.0)

    monkeypatch.setattr(master_mod.Master, "serve", stuck_serve)
    algo = make_algorithm("dana-zero", HP)
    cfg = _cfg("thread", grads=20, rpc_timeout=0.5)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="master failed to shut down"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)
    # bounded: deadline is max(rpc_timeout, 2s), nowhere near the 30s nap
    assert time.monotonic() - t0 < 15.0


# ---------------------------------------------------------------------------
# regression: fan-out telemetry must flush or be counted, never vanish
# ---------------------------------------------------------------------------
def _group(shards, tele, drops):
    msg = GradMsg(0, grad=object(), view=None, view_step=0, t_send=1.0)
    return msg, _ReplyGroup(
        msg, shards,
        tele_cb=lambda **kw: tele.append(kw),
        drop_cb=lambda: drops.append(1))


def test_reply_group_flushes_when_shard0_meta_lands_last():
    tele, drops = [], []
    msg, g = _group(2, tele, drops)
    g.add_telemetry(1, worker=0, step=3, lag=1, t=0.0, d2=1.0, g2=2.0)
    g.shard_reply(1, Reply(view="v1", step=3))
    # shard 0 applies (and carries the canonical meta) last
    g.add_telemetry(0, worker=0, step=3, lag=1, t=1.5, d2=0.5, g2=0.25)
    g.shard_reply(0, Reply(view="v0", step=3))
    assert drops == []
    assert len(tele) == 1
    assert tele[0]["d2"] == pytest.approx(1.5)
    assert tele[0]["g2"] == pytest.approx(2.25)
    assert tele[0]["t"] == pytest.approx(1.5)


def test_reply_group_counts_drop_on_failed_shard():
    tele, drops = [], []
    msg, g = _group(2, tele, drops)
    g.add_telemetry(0, worker=0, step=3, lag=1, t=1.5, d2=0.5, g2=0.25)
    g.shard_reply(0, Reply(view="v0", step=3))
    g.shard_reply(1, None)        # shard 1 rejected: group fails
    assert msg.wait_reply(1.0) is None
    assert tele == []             # partial sums must not flush...
    assert drops == [1]           # ...but the loss is counted


def test_reply_group_pull_only_is_not_a_drop():
    tele, drops = [], []
    msg, g = _group(2, tele, drops)
    g.shard_reply(0, Reply(view="v0", step=3))
    g.shard_reply(1, Reply(view="v1", step=3))
    assert msg.wait_reply(1.0) is not None
    assert tele == [] and drops == []


def test_sharded_run_reports_zero_drops_when_healthy():
    stats = {}
    algo = make_algorithm("dana-zero", HP)
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                _cfg("thread", shards=2), stats_out=stats)
    assert stats["telemetry_dropped"] == 0


# ---------------------------------------------------------------------------
# regression: hot_rows upper bound is INCLUSIVE (r1 == rows_total is the
# full-height range) and the error message must say so
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 2])
def test_hot_rows_full_height_range_is_valid(shards):
    rows = FlatSpec.from_tree(PARAMS0).rows
    stats = {}
    algo = make_algorithm("dana-zero", HP)
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                _cfg("thread", shards=shards, grads=12,
                     hot_rows=((0, rows), None)),
                stats_out=stats)
    assert stats["applied"] == 12


@pytest.mark.parametrize("shards", [1, 2])
def test_hot_rows_past_end_rejected_with_inclusive_message(shards):
    rows = FlatSpec.from_tree(PARAMS0).rows
    algo = make_algorithm("dana-zero", HP)
    with pytest.raises(ValueError,
                       match=r"0 <= r0 < r1 <= \d+ \(r1 bound inclusive\)"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                    _cfg("thread", shards=shards, grads=12,
                         hot_rows=((0, rows + 1), None)))
