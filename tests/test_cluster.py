"""Cluster runtime tests: engine equivalence, coalescing, faults.

The load-bearing contract is backend equivalence: in deterministic mode
the threaded parameter-server runtime must reproduce ``run_simulation``
*bit-for-bit* — master parameters, telemetry, and eval curves — so the
discrete-event simulator remains the reference semantics for every
algorithm running on the cluster.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (ClusterConfig, FaultPlan, Mailbox, Master,
                           run_cluster)
from repro.core import (GammaModel, HyperParams, SimulationConfig,
                        make_algorithm, run_simulation)
from repro.core.metrics import History
from repro.data.synthetic import ClassificationTask
from repro.models.toy import make_classifier_fns

HP = HyperParams(lr=0.05, momentum=0.9)
TASK = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
INIT, GRAD_FN, MAKE_EVAL = make_classifier_fns([8, 16, 4])
PARAMS0 = INIT(jax.random.PRNGKey(0))
EVAL_FN = MAKE_EVAL(TASK.eval_batch(32))


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# Two separately compiled XLA programs do not round alike (the CPU
# backend contracts a*b + c into one FMA in some programs and not in
# others), so a contract between two compiled passes compares each with
# the op-by-op reference, to 8 f32 ulps of the reference's largest
# magnitude: a k = 4 message chain runs ~10 f32 ops per element.
CHAIN_ULPS = 8
F32_EPS = float(np.finfo(np.float32).eps)


def _assert_close_to_ref(got, ref):
    for x, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref),
                    strict=True):
        r = np.asarray(r, np.float32)
        np.testing.assert_allclose(
            np.asarray(x, np.float32), r, rtol=CHAIN_ULPS * F32_EPS,
            atol=CHAIN_ULPS * F32_EPS * float(np.max(np.abs(r))))


def _eager_receive_sends(algo, state, ids, grads, nows):
    """The plain reference: receive->send message by message, op by op
    (no jit, so no fusion)."""
    views = []
    with jax.disable_jit():
        for i, g, t in zip(ids, grads, nows):
            state, view = algo.receive_send(state, jnp.int32(i), g,
                                            jnp.float32(t))
            views.append(view)
    return state, views


def _run_engine(name, *, workers, grads, seed=5, hetero=False):
    algo = make_algorithm(name, HP)
    gm = (GammaModel.heterogeneous_env(seed=seed) if hetero
          else GammaModel(seed=seed))
    cfg = SimulationConfig(num_workers=workers, total_grads=grads,
                           eval_every=20, exec_model=gm)
    return run_simulation(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, EVAL_FN)


def _run_cluster(name, *, workers, grads, seed=5, hetero=False, **kw):
    algo = make_algorithm(name, HP)
    gm = (GammaModel.heterogeneous_env(seed=seed) if hetero
          else GammaModel(seed=seed))
    cfg = ClusterConfig(num_workers=workers, total_grads=grads,
                        eval_every=20, exec_model=gm,
                        mode=kw.pop("mode", "deterministic"), **kw)
    return run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, EVAL_FN)


# ---------------------------------------------------------------------------
# deterministic mode == discrete-event engine, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["asgd", "dana-zero"])
def test_deterministic_cluster_matches_engine(name):
    h_e = _run_engine(name, workers=4, grads=80)
    h_c = _run_cluster(name, workers=4, grads=80)
    _assert_trees_equal(h_e.final_params, h_c.final_params)
    assert h_e.time == h_c.time
    assert h_e.worker == h_c.worker
    assert h_e.lag == h_c.lag
    assert h_e.gap == h_c.gap
    assert h_e.grad_norm == h_c.grad_norm
    assert h_e.eval_loss == h_c.eval_loss
    assert h_e.eval_step == h_c.eval_step


def test_deterministic_cluster_matches_engine_heterogeneous():
    """Heterogeneous gamma draws stress the event-order replay: every
    draw of the shared sampler must happen in engine order."""
    h_e = _run_engine("dana-slim", workers=3, grads=60, hetero=True)
    h_c = _run_cluster("dana-slim", workers=3, grads=60, hetero=True)
    _assert_trees_equal(h_e.final_params, h_c.final_params)
    assert h_e.time == h_c.time
    assert h_e.gap == h_c.gap


# ---------------------------------------------------------------------------
# coalesced receive
# ---------------------------------------------------------------------------
def _make_master(name, n, *, use_kernel=False, flat=None, telemetry=False):
    algo = make_algorithm(name, HP)
    state = algo.init(PARAMS0, n)
    master = Master(algo, state, mailbox=Mailbox(), history=History(),
                    stop=threading.Event(), total_grads=100,
                    coalesce=8, use_kernel=use_kernel, flat=flat,
                    record_telemetry=telemetry)
    return algo, state, master


def _grads(k, seed=0):
    gs = []
    for j in range(k):
        gs.append(jax.jit(GRAD_FN)(PARAMS0, TASK.batch(j % 3, seed + j)))
    return tuple(gs)


def test_coalesced_pass_matches_sequential_receive():
    """One fused k-message dispatch must equal k sequential
    receive->send rounds — coalescing is a dispatch optimization, not a
    semantic change.  The two are separate compilations, so each is
    held to the op-by-op reference."""
    k = 4
    algo, state, master = _make_master("dana-zero", n=4)
    ids = [0, 2, 1, 2]
    nows = [1.0, 2.5, 3.0, 4.0]
    grads = _grads(k)
    fn = master._get_fused(k, telemetry=False)
    fused_state, fused_views, _, _ = fn(
        state, jnp.asarray(ids, jnp.int32), jnp.asarray(nows, jnp.float32),
        grads, None)
    # the per-message path: one jitted receive->send dispatch per message
    # (exactly what the master does at k=1)
    one = master._get_fused(1, telemetry=False)
    seq_state = state
    seq_views = []
    for i, g, t in zip(ids, grads, nows):
        seq_state, views1, _, _ = one(
            seq_state, jnp.asarray([i], jnp.int32),
            jnp.asarray([t], jnp.float32), (g,), None)
        seq_views.append(views1[0])
    ref_state, ref_views = _eager_receive_sends(algo, state, ids, grads,
                                                nows)
    for got_state, got_views in ((fused_state, fused_views),
                                 (seq_state, seq_views)):
        for key in ("theta0", "v", "v0"):
            _assert_close_to_ref(got_state[key], ref_state[key])
        for a, r in zip(got_views, ref_views, strict=True):
            _assert_close_to_ref(a, r)


def test_flat_master_bounds_its_run_ahead():
    """The flat master dispatches a receive only while at most RUN_AHEAD
    earlier ones are unfinished: it waits on the oldest one's ``done``
    scalar first, so the device queue, and the gradients and views it
    holds, stays RUN_AHEAD + 1 receives deep."""
    from repro.cluster.mailbox import GradMsg
    from repro.cluster.master import RUN_AHEAD

    class Done:                        # a receive the device still runs
        ready = False

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.ready = True
            return self

    _, _, m = _make_master("dana-zero", n=2, use_kernel=True)
    dones = []

    def receive(st, ids, nows, grads, views):
        assert sum(not d.ready for d in dones) <= RUN_AHEAD
        dones.append(Done())
        return st, grads, None, None, dones[-1]

    m._fused[("flat", 1, False)] = receive
    g = m._flat_algo.spec.pack(_grads(1)[0])
    n = 2 * RUN_AHEAD + 3
    for seq in range(n):
        m._apply([GradMsg(seq % 2, g, None, 0, 0.0, seq=seq)])
    assert [d.ready for d in dones] == ([True] * (n - RUN_AHEAD - 1)
                                       + [False] * (RUN_AHEAD + 1))


def test_kernel_routing_matches_algorithm_path():
    """All three master paths — generic tree, PR 1's legacy per-message
    dana_update kernel (flat=False), and the batched flat kernel — must
    agree under a constant learning rate: each matches the op-by-op
    reference of the algorithm path."""
    k = 4
    algo, state, m_plain = _make_master("dana-zero", n=4,
                                        use_kernel=False)
    _, _, m_legacy = _make_master("dana-zero", n=4, use_kernel=True,
                                  flat=False)
    _, _, m_flat = _make_master("dana-zero", n=4, use_kernel=True)
    assert not m_legacy.state_is_flat and m_flat.state_is_flat
    ids = jnp.asarray([1, 3, 1, 0], jnp.int32)
    nows = jnp.zeros((k,), jnp.float32)
    grads = _grads(k, seed=7)
    spec = m_flat._flat_algo.spec
    s_p, v_p, _, _ = m_plain._get_fused(k, False)(state, ids, nows, grads,
                                                  None)
    s_k, v_k, _, _ = m_legacy._get_fused(k, False)(state, ids, nows, grads,
                                                   None)
    s_f, v_f, _, _, _ = m_flat._get_fused_flat(k, False)(
        m_flat._flat_state, ids, nows,
        tuple(spec.pack(g) for g in grads), None)  # unstacked wire
    v_f = tuple(spec.unpack(v) for v in v_f)
    s_f = m_flat._flat_algo.tree_state(s_f)
    s_r, v_r = _eager_receive_sends(algo, state, ids, grads, nows)
    for s_got in (s_p, s_k, s_f):
        for key in ("theta0", "v", "v0"):
            _assert_close_to_ref(s_got[key], s_r[key])
    for v_got in (v_p, v_k, v_f):
        for a, r in zip(v_got, v_r, strict=True):
            _assert_close_to_ref(a, r)


def test_master_capacity_coalescing_speedup():
    """Coalesced receive (k=8) must beat per-message receive in master
    updates/sec — the App. C.1 bottleneck attack.  The fused pass
    amortizes one dispatch over k messages; the measured margin is ~4x.
    Wall-clock assertions flake on loaded machines, so each side takes
    the best of 3 trials and the bar is a loose 1.15x (the full
    measurement lives in benchmarks/bench_cluster.py)."""
    import time
    _, state, master = _make_master("dana-zero", n=8)
    grad = _grads(1)[0]

    def throughput(k, reps):
        fn = master._get_fused(k, telemetry=False)
        ids = jnp.asarray([j % 8 for j in range(k)], jnp.int32)
        nows = jnp.zeros((k,), jnp.float32)
        grads = tuple(grad for _ in range(k))
        s, *_ = fn(state, ids, nows, grads, None)
        jax.block_until_ready(s["theta0"])          # compile
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            s = state
            for _ in range(reps):
                s, *_ = fn(s, ids, nows, grads, None)
            jax.block_until_ready(s["theta0"])
            best = max(best, k * reps / (time.perf_counter() - t0))
        return best

    t1 = throughput(1, reps=120)
    t8 = throughput(8, reps=20)
    assert t8 > 1.15 * t1, (t1, t8)


def test_free_mode_coalescing_completes():
    algo = make_algorithm("dana-zero", HP)
    cfg = ClusterConfig(num_workers=8, total_grads=240, mode="free",
                        coalesce=4, record_telemetry=False)
    stats = {}
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg,
                       stats_out=stats)
    assert stats["applied"] == 240
    assert sum(stats["grads_per_worker"].values()) == 240
    assert stats["mean_coalesce"] >= 1.0
    assert stats["use_kernel"] is True          # auto-routed for dana-zero
    assert hist.final_params is not None


def test_telemetry_recorded_in_live_mode():
    algo = make_algorithm("multi-asgd", HP)
    cfg = ClusterConfig(num_workers=4, total_grads=120, mode="free",
                        coalesce=2, eval_every=40)
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, EVAL_FN)
    assert len(hist.time) == len(hist.gap) == len(hist.lag) == 120
    assert all(l >= 0 for l in hist.lag)
    assert hist.eval_loss          # eval curve recorded
    assert sorted(hist.step) == list(range(1, 121))


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------
def test_dropout_worker_rejoins():
    algo = make_algorithm("dana-slim", HP)
    plan = FaultPlan(seed=1, dropout=((2, 20, 160),))
    cfg = ClusterConfig(num_workers=4, total_grads=240, mode="free",
                        coalesce=2, faults=plan, record_telemetry=False)
    stats = {}
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, stats_out=stats)
    counts = stats["grads_per_worker"]
    assert stats["applied"] == 240
    # the dropped worker contributed, but noticeably less than the rest
    assert counts[2] > 0
    assert counts[2] < min(counts[w] for w in (0, 1, 3))


def test_stalls_deterministic_and_reproducible():
    """In deterministic mode injected stalls inflate *virtual* time, so
    the faulty run is still exactly reproducible."""
    def run():
        return _run_cluster("dana-zero", workers=4, grads=60,
                            faults=FaultPlan(seed=3, stall_prob=0.25,
                                             stall_scale=4.0))
    h1, h2 = run(), run()
    assert h1.time == h2.time
    assert h1.gap == h2.gap
    _assert_trees_equal(h1.final_params, h2.final_params)
    # and the stalls actually moved the schedule vs the clean run
    h0 = _run_cluster("dana-zero", workers=4, grads=60)
    assert h0.time != h1.time


def test_reordering_preserves_totals():
    algo = make_algorithm("asgd", HP)
    plan = FaultPlan(seed=2, reorder_prob=1.0)
    cfg = ClusterConfig(num_workers=6, total_grads=180, mode="free",
                        coalesce=4, faults=plan)
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)
    assert len(hist.step) == 180
    assert all(l >= 0 for l in hist.lag)


def test_dropout_rejected_in_deterministic_mode():
    algo = make_algorithm("asgd", HP)
    cfg = ClusterConfig(num_workers=2, total_grads=10,
                        mode="deterministic",
                        faults=FaultPlan(dropout=((0, 1, 2),)))
    with pytest.raises(ValueError, match="dropout"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
def test_bounded_mailbox_applies_backpressure():
    algo = make_algorithm("asgd", HP)
    cfg = ClusterConfig(num_workers=6, total_grads=120, mode="free",
                        coalesce=2, mailbox_capacity=2,
                        record_telemetry=False)
    stats = {}
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, stats_out=stats)
    assert stats["applied"] == 120
    # a capacity-2 queue can never serve a coalesce window above 2
    assert max(stats["coalesce_counts"]) <= 2


def test_use_kernel_rejected_for_ineligible():
    # the flat family closed over asgd/lwp/dana-hetero in PR 5; easgd's
    # replica exchange remains the ineligible negative case
    algo = make_algorithm("easgd", HP)
    cfg = ClusterConfig(num_workers=2, total_grads=10, mode="free",
                        use_kernel=True)
    with pytest.raises((ValueError, RuntimeError)):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)


def test_asgd_auto_routes_flat_in_live_mode():
    """asgd joined the flat family (gamma = 0): live modes auto-route it
    through the batched kernel and the run completes."""
    algo = make_algorithm("asgd", HP)
    cfg = ClusterConfig(num_workers=4, total_grads=120, mode="free",
                        coalesce=4, record_telemetry=False)
    stats = {}
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, stats_out=stats)
    assert stats["applied"] == 120
    assert stats["use_kernel"] is True


def test_cluster_cli_smoke(tmp_path):
    from repro.launch import cluster as cli
    out = tmp_path / "cluster.json"
    summary = cli.main(["--workers", "2", "--grads", "30", "--mode",
                        "deterministic", "--dim", "8", "--batch", "8",
                        "--eval-every", "10", "--compare-engine",
                        "--out", str(out)])
    assert summary["engine_max_param_diff"] == 0.0
    assert out.exists()
