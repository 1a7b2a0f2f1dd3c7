"""Row-sharded multi-master: equivalence and fault isolation.

The load-bearing contract is *bit-identity*: because every ELEMENTWISE
flat-family update rule is per row, splitting the flat buffers into S
contiguous row ranges and applying the SAME message sequence per shard
must reproduce the single flat master exactly — state, views, and (in
deterministic mode) the whole engine replay.  That now includes the
sent-snapshot members dc-asgd and dana-dc (the snapshot slab shards by
the same row ranges).  Gap-aware (ga-asgd) needs a global norm per
message; its shards rendezvous in a ``_NormExchange`` and match the
single master to float tolerance (the per-shard partial sum reorders
the reduction).  Faults confined to one shard must leave the other
shards' replay bit-for-bit unchanged.

Eval snapshots use a common applied-count watermark: fused chunks never
straddle a multiple of ``eval_every``, so every shard contributes the
state at exactly the same message count even when their drain batches
differ (the cross-shard snapshot-consistency regression test below).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (ClusterConfig, FaultPlan, Mailbox, Master,
                           ShardedMaster, run_cluster)
from repro.cluster.mailbox import FanoutMailbox, GradMsg
from repro.core import (HyperParams, REGISTRY, SimulationConfig,
                        make_algorithm, run_simulation)
from repro.core.metrics import History
from repro.data.synthetic import ClassificationTask
from repro.kernels.flat_update import kernel_eligible, shard_bitexact
from repro.models.toy import make_classifier_fns

HP = HyperParams(lr=0.05, momentum=0.9)
TASK = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
INIT, GRAD_FN, MAKE_EVAL = make_classifier_fns([8, 16, 4])
PARAMS0 = INIT(jax.random.PRNGKey(0))
EVAL_FN = MAKE_EVAL(TASK.eval_batch(32))

ELIGIBLE = sorted(n for n in REGISTRY
                  if kernel_eligible(make_algorithm(n, HP)))
# the shard-bit-exact (elementwise) subset: everything but ga-asgd
ELEMENTWISE = sorted(n for n in ELIGIBLE
                     if shard_bitexact(make_algorithm(n, HP)))


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _grads(k, seed=0):
    return tuple(jax.jit(GRAD_FN)(PARAMS0, TASK.batch(j % 3, seed + j))
                 for j in range(k))


# duplicate worker ids inside one batch: momentum chaining across shards
BATCHES = [
    ([1, 3, 1, 0], 11),
    ([2, 2, 2, 2], 29),
    ([0, 1, 2, 3], 47),
]


def _drive_single(name, n):
    """Apply BATCHES through the single flat master's fused pass."""
    algo = make_algorithm(name, HP)
    master = Master(algo, algo.init(PARAMS0, n), mailbox=Mailbox(),
                    history=History(), stop=threading.Event(),
                    total_grads=100, coalesce=8, use_kernel=True,
                    record_telemetry=False)
    spec = master._flat_algo.spec
    st, out = master._flat_state, []
    for ids, seed in BATCHES:
        k = len(ids)
        fn = master._get_fused_flat(k, False)
        st, views, _, _, _ = fn(st, jnp.asarray(ids, jnp.int32),
                             jnp.zeros((k,), jnp.float32),
                             tuple(spec.pack(g) for g in _grads(k, seed)),
                             None)
        out.extend(views)
    master._flat_state = st
    return master, out


def _drive_sharded(name, n, shards, perm_shard=None, perm=None):
    """Apply BATCHES shard-by-shard (optionally permuting ONE shard's
    message order, the out-of-order-delivery fault)."""
    algo = make_algorithm(name, HP)
    sm = ShardedMaster(algo, algo.init(PARAMS0, n), shards=shards,
                       history=History(), stop=threading.Event(),
                       total_grads=100, coalesce=8,
                       record_telemetry=False)
    spec = sm.spec
    out = []
    for ids, seed in BATCHES:
        k = len(ids)
        g_flat = [spec.pack(g) for g in _grads(k, seed)]
        per_shard = []
        for srv in sm.shards_:
            order = (perm if perm is not None and srv.sid == perm_shard
                     else list(range(k)))
            fn = srv._get_fused(k, False)
            st, views, _, _ = fn(
                srv.state,
                jnp.asarray([ids[j] for j in order], jnp.int32),
                jnp.zeros((k,), jnp.float32),
                jnp.stack([g_flat[j][srv.r0:srv.r1] for j in order]),
                None)
            srv.state = st
            per_shard.append(views)
        out.extend(
            jnp.concatenate([per_shard[s][j] for s in range(shards)],
                            axis=0)
            for j in range(k))
    return sm, out


# ---------------------------------------------------------------------------
# equivalence: sharded == single flat master, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("name", ELEMENTWISE)
def test_sharded_equals_single_master(name, shards):
    """S row-range shards applying the same sequence must reproduce the
    single flat master exactly — full state AND every worker view —
    for every elementwise kernel-eligible algorithm (the sent-snapshot
    members dc-asgd / dana-dc included), duplicate ids included."""
    single, views_s = _drive_single(name, n=4)
    sharded, views_h = _drive_sharded(name, n=4, shards=shards)
    _assert_trees_equal(single.master_params(), sharded.master_params())
    _assert_trees_equal(single.state, sharded.state)
    assert len(views_s) == len(views_h) == 12
    for a, b in zip(views_s, views_h):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_gap_exchange_matches_single_master(shards):
    """ga-asgd's three-step shard pipeline (partial -> combined gap2 ->
    apply -> combined ||v'||^2 -> avg_step) must reproduce the single
    flat master to float tolerance — per-shard partial sums reorder the
    norm reduction, which is exactly why ga-asgd is shard-eligible but
    not shard-bit-exact."""
    single, views_s = _drive_single("ga-asgd", n=4)
    algo = make_algorithm("ga-asgd", HP)
    sm = ShardedMaster(algo, algo.init(PARAMS0, 4), shards=shards,
                       history=History(), stop=threading.Event(),
                       total_grads=100, coalesce=8,
                       record_telemetry=False)
    assert sm.coalesce == 8            # PR-5: the coalesce=1 clamp is gone
    spec = sm.spec
    views_h = []
    for ids, seed in BATCHES:
        for j, wid in enumerate(ids):
            g_flat = spec.pack(_grads(len(ids), seed)[j])
            i32 = jnp.int32(wid)
            # the serve loop's exchange, driven synchronously: combine
            # the S partials in shard order, then apply per shard
            parts = [float(srv._gap_partial_jit(srv.state, i32))
                     for srv in sm.shards_]
            gap2 = float(np.float32(sum(np.float32(p) for p in parts)))
            outs = []
            for srv in sm.shards_:
                st, hat, vn2, lr, vs, _, _ = srv._gap_apply_jit(
                    srv.state, i32, g_flat[srv.r0:srv.r1],
                    jnp.float32(gap2), None)
                outs.append((srv, st, hat, vn2, lr, vs))
            vn2_t = float(np.float32(sum(np.float32(float(o[3]))
                                         for o in outs)))
            for srv, st, hat, vn2, lr, vs in outs:
                srv.state = srv._gap_finish_jit(st, jnp.float32(vn2_t),
                                                lr, vs)
            views_h.append(jnp.concatenate(
                [o[2] for o in outs], axis=0))
    for a, b in zip(jax.tree.leaves(single.state),
                    jax.tree.leaves(sm.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    for a, b in zip(views_s, views_h):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_sharded_gap_batched_exchange_matches_single_master():
    """The lifted coalesce=1 restriction: S shard threads draining REAL
    batches through ``_apply_gap`` (the streaming _NormExchange ring,
    two combines per message) reproduce the single flat master's
    batched gap-aware pass to float tolerance."""
    shards = 2
    single, views_s = _drive_single("ga-asgd", n=4)
    algo = make_algorithm("ga-asgd", HP)
    sm = ShardedMaster(algo, algo.init(PARAMS0, 4), shards=shards,
                       history=History(), stop=threading.Event(),
                       total_grads=100, coalesce=4,
                       record_telemetry=False)
    spec = sm.spec
    views_by_shard = [[] for _ in range(shards)]
    for ids, seed in BATCHES:
        g_flat = [spec.pack(g) for g in _grads(len(ids), seed)]
        msgs_by_shard = [
            [GradMsg(wid, g_flat[j][srv.r0:srv.r1], None, 0, 0.0)
             for j, wid in enumerate(ids)]
            for srv in sm.shards_
        ]
        # both shards must run concurrently: each message's exchange
        # blocks until every shard has published its partial
        threads = [
            threading.Thread(target=srv._apply, args=(msgs,))
            for srv, msgs in zip(sm.shards_, msgs_by_shard)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        for s, msgs in enumerate(msgs_by_shard):
            views_by_shard[s].extend(m.wait_reply(1.0).view for m in msgs)
    for a, b in zip(jax.tree.leaves(single.state),
                    jax.tree.leaves(sm.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    for j, v_single in enumerate(views_s):
        v_shard = jnp.concatenate(
            [views_by_shard[s][j] for s in range(shards)], axis=0)
        np.testing.assert_allclose(np.asarray(v_shard),
                                   np.asarray(v_single),
                                   rtol=2e-5, atol=2e-6)


def test_sharded_gap_reorder_injection_reclamps_to_per_message():
    """The norm exchange pairs partials by applied count, so gap-aware
    shards must apply the IDENTICAL order: with per-shard (reorder)
    injectors attached the coalesce window re-clamps to 1 — a 1-message
    chunk cannot be permuted — and the faulted run still completes."""
    algo = make_algorithm("ga-asgd", HP)
    inj = [FaultPlan(seed=2, reorder_prob=1.0, reorder_shards=(0,))]
    from repro.cluster.faults import FaultInjector
    injectors = [FaultInjector(inj[0], 0, 32, shard_id=s)
                 for s in range(2)]
    sm = ShardedMaster(algo, algo.init(PARAMS0, 4), shards=2,
                       history=History(), stop=threading.Event(),
                       total_grads=10, coalesce=4, injectors=injectors,
                       record_telemetry=False)
    assert sm.coalesce == 1
    # a stall-only plan never permutes chunk order: batching survives
    stall_inj = [FaultInjector(FaultPlan(seed=1, stall_prob=0.5), 0, 32,
                               shard_id=s) for s in range(2)]
    sm2 = ShardedMaster(algo, algo.init(PARAMS0, 4), shards=2,
                        history=History(), stop=threading.Event(),
                        total_grads=10, coalesce=4, injectors=stall_inj,
                        record_telemetry=False)
    assert sm2.coalesce == 4
    cfg = ClusterConfig(num_workers=4, total_grads=80, mode="free",
                        coalesce=4, shards=2, record_telemetry=False,
                        faults=FaultPlan(seed=2, reorder_prob=1.0))
    stats = {}
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, stats_out=stats)
    assert stats["applied"] == 80


def test_sharded_gap_free_mode_coalesced_completes():
    """End to end: a free-mode ga-asgd sharded cluster with coalesce > 1
    (the ring exchange under real worker + shard threads) completes."""
    algo = make_algorithm("ga-asgd", HP)
    cfg = ClusterConfig(num_workers=4, total_grads=120, mode="free",
                        coalesce=4, shards=2, record_telemetry=False)
    stats = {}
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, stats_out=stats)
    assert stats["applied"] == 120
    assert stats["shard_applied"] == [120, 120]


def test_sharded_gap_deterministic_cluster_matches_single():
    """End to end: the threaded ga-asgd sharded cluster (deterministic
    mode, real _NormExchange rendezvous) tracks the single flat master
    run to float tolerance."""
    def run(shards):
        algo = make_algorithm("ga-asgd", HP)
        cfg = ClusterConfig(num_workers=4, total_grads=60,
                            mode="deterministic", shards=shards,
                            use_kernel=True, record_telemetry=False)
        return run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)

    h1, h3 = run(1), run(3)
    for a, b in zip(jax.tree.leaves(h1.final_params),
                    jax.tree.leaves(h3.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6)


def test_sharded_deterministic_cluster_matches_engine():
    """End to end: the threaded sharded cluster in deterministic mode
    replays the discrete-event engine bit-for-bit (params, telemetry
    identity; gap is allclose — the sharded gap sums S partials)."""
    def cluster(shards):
        algo = make_algorithm("dana-zero", HP)
        cfg = ClusterConfig(num_workers=4, total_grads=80, eval_every=20,
                            mode="deterministic", shards=shards)
        return run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg,
                           EVAL_FN)

    algo = make_algorithm("dana-zero", HP)
    h_e = run_simulation(algo, GRAD_FN, PARAMS0, TASK.batch,
                         SimulationConfig(num_workers=4, total_grads=80,
                                          eval_every=20), EVAL_FN)
    h_c = cluster(shards=3)
    _assert_trees_equal(h_e.final_params, h_c.final_params)
    assert h_e.time == h_c.time
    assert h_e.worker == h_c.worker
    assert h_e.lag == h_c.lag
    assert h_e.eval_step == h_c.eval_step
    np.testing.assert_allclose(h_c.eval_loss, h_e.eval_loss, rtol=1e-6)
    np.testing.assert_allclose(h_c.gap, h_e.gap, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(h_c.grad_norm, h_e.grad_norm, rtol=1e-5)


@pytest.mark.parametrize("name", ["multi-asgd", "dana-nadam", "dc-asgd",
                                  "dana-dc", "asgd", "lwp",
                                  "dana-hetero"])
def test_sharded_deterministic_matches_single_flat(name):
    """Sharded vs single-master flat cluster, same deterministic run:
    identical parameters for the non-DANA family members, the
    sent-snapshot members, and the PR-5 additions (asgd's gamma=0
    update, lwp's tau look-ahead, dana-hetero's rate-weighted send —
    per-row, so row sharding stays bit-exact; the rate lane replicates
    through the copied-scalar path)."""
    def run(shards):
        algo = make_algorithm(name, HP)
        cfg = ClusterConfig(num_workers=3, total_grads=60,
                            mode="deterministic", shards=shards,
                            use_kernel=True, record_telemetry=False)
        return run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)

    _assert_trees_equal(run(1).final_params, run(4).final_params)


def test_sharded_free_mode_completes():
    algo = make_algorithm("dana-slim", HP)
    cfg = ClusterConfig(num_workers=8, total_grads=240, mode="free",
                        coalesce=4, shards=4, record_telemetry=False)
    stats = {}
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg,
                       stats_out=stats)
    assert stats["applied"] == 240
    assert stats["shards"] == 4
    assert stats["shard_applied"] == [240] * 4
    assert sum(stats["grads_per_worker"].values()) == 240
    assert hist.final_params is not None


def test_eval_watermark_consistency_under_coalescing():
    """Regression (ROADMAP follow-up: cross-shard eval snapshot
    consistency).  With deep queues and coalesce=8 > eval_every=3, drain
    batches straddle eval boundaries; the serve loop must split chunks
    at the watermark so every eval observes the state at EXACTLY a
    multiple of eval_every applied messages — identical across a k=1
    master, a deep-coalescing master, and every shard of a sharded
    master."""
    total, every = 24, 3
    ids = [j % 4 for j in range(total)]
    grads = _grads(total, seed=9)

    def run(shards, coalesce):
        algo = make_algorithm("dana-zero", HP)
        stop = threading.Event()
        kw = dict(history=History(), stop=stop, total_grads=total,
                  coalesce=coalesce, eval_fn=EVAL_FN, eval_every=every,
                  record_telemetry=False)
        if shards == 1:
            mb = Mailbox()
            m = Master(algo, algo.init(PARAMS0, 4), mailbox=mb,
                       use_kernel=True, **kw)
            spec = m._flat_algo.spec
            for wid, g in zip(ids, grads):
                mb.put(GradMsg(wid, spec.pack(g), None, 0, 0.0), stop)
        else:
            m = ShardedMaster(algo, algo.init(PARAMS0, 4),
                              shards=shards, **kw)
            for wid, g in zip(ids, grads):
                gf = m.spec.pack(g)
                m.frontdoor.put(
                    GradMsg(wid, tuple(sub.take(gf) for sub in m.subs),
                            None, 0, 0.0), stop)
        m.serve()
        return m

    ref = run(1, coalesce=1)               # per-message: exact by def.
    deep = run(1, coalesce=8)
    shard = run(2, coalesce=8)
    marks = list(range(every, total + 1, every))
    assert ref.history.eval_step == marks
    # coalescing really happened (the test would be vacuous otherwise)
    assert max(deep.coalesce_counts) > 1
    assert max(shard.coalesce_counts) > 1
    curve = dict(zip(ref.history.eval_step, ref.history.eval_loss))
    for m in (deep, shard):
        # shard threads may RECORD evals out of order; the watermark
        # contract is about the step -> snapshot mapping
        assert sorted(m.history.eval_step) == marks
        assert dict(zip(m.history.eval_step,
                        m.history.eval_loss)) == curve


def test_sharded_live_telemetry_and_eval():
    algo = make_algorithm("dana-zero", HP)
    cfg = ClusterConfig(num_workers=4, total_grads=120, mode="free",
                        coalesce=2, shards=2, eval_every=40)
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, EVAL_FN)
    # every message applied on EVERY shard produces exactly one row
    assert len(hist.time) == len(hist.gap) == len(hist.lag) == 120
    assert all(l >= 0 for l in hist.lag)
    assert sorted(hist.step) == list(range(1, 121))
    assert hist.eval_loss                      # assembled-snapshot evals


# ---------------------------------------------------------------------------
# fault isolation
# ---------------------------------------------------------------------------
def test_reorder_on_one_shard_leaves_others_bit_identical():
    """Out-of-order delivery on shard 0's link must not perturb any other
    shard's replay: their row ranges stay bit-for-bit equal to the clean
    run, while shard 0's rows actually change."""
    clean, _ = _drive_sharded("dana-zero", n=4, shards=3)
    fault, _ = _drive_sharded("dana-zero", n=4, shards=3,
                              perm_shard=0, perm=[2, 0, 3, 1])
    diff0 = np.max(np.abs(
        np.asarray(clean.shards_[0].state["theta"])
        - np.asarray(fault.shards_[0].state["theta"])))
    assert diff0 > 0.0                        # the fault was real
    for s in (1, 2):
        for key in ("theta", "v", "v0"):
            np.testing.assert_array_equal(
                np.asarray(clean.shards_[s].state[key]),
                np.asarray(fault.shards_[s].state[key]))


def test_sharded_stalls_deterministic_and_reproducible():
    """Worker stalls inflate virtual time only: the sharded deterministic
    run is reproducible AND bit-identical to the single-master run under
    the same fault plan."""
    def run(shards):
        algo = make_algorithm("dana-zero", HP)
        cfg = ClusterConfig(num_workers=4, total_grads=60,
                            mode="deterministic", shards=shards,
                            use_kernel=True,
                            faults=FaultPlan(seed=3, stall_prob=0.25,
                                             stall_scale=4.0))
        return run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg,
                           EVAL_FN)

    h1, h2, h_single = run(2), run(2), run(1)
    assert h1.time == h2.time == h_single.time
    _assert_trees_equal(h1.final_params, h2.final_params)
    _assert_trees_equal(h1.final_params, h_single.final_params)


def test_sharded_reorder_targets_only_listed_shards():
    """reorder_shards=(1,) with reorder_prob=1: the run completes and the
    per-shard injectors leave shard 0 untouched (free mode, coalesce>1 so
    reordering actually fires)."""
    algo = make_algorithm("dana-zero", HP)
    plan = FaultPlan(seed=2, reorder_prob=1.0, reorder_shards=(1,))
    cfg = ClusterConfig(num_workers=6, total_grads=180, mode="free",
                        coalesce=4, shards=2, faults=plan)
    stats = {}
    hist = run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg,
                       stats_out=stats)
    assert stats["applied"] == 180
    assert len(hist.step) == 180
    assert all(l >= 0 for l in hist.lag)


def test_sharded_dropout_worker_rejoins():
    """Dropout/rejoin under sharding: the rejoin pull fans out to every
    shard and the returning worker keeps contributing."""
    algo = make_algorithm("dana-slim", HP)
    plan = FaultPlan(seed=1, dropout=((2, 20, 160),))
    cfg = ClusterConfig(num_workers=4, total_grads=240, mode="free",
                        coalesce=2, shards=2, faults=plan,
                        record_telemetry=False)
    stats = {}
    run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, stats_out=stats)
    counts = stats["grads_per_worker"]
    assert stats["applied"] == 240
    assert counts[2] > 0
    assert counts[2] < min(counts[w] for w in (0, 1, 3))


# ---------------------------------------------------------------------------
# plumbing / guard rails
# ---------------------------------------------------------------------------
def test_sharded_rejects_ineligible_algorithm():
    # easgd's replica exchange is outside the flat family (asgd and lwp
    # joined it in PR 5, so they no longer serve as the negative case)
    algo = make_algorithm("easgd", HP)
    with pytest.raises(ValueError, match="eligible"):
        ShardedMaster(algo, algo.init(PARAMS0, 2), shards=2,
                      history=History(), stop=threading.Event(),
                      total_grads=10)
    cfg = ClusterConfig(num_workers=2, total_grads=10, mode="free",
                        shards=2)
    with pytest.raises((ValueError, RuntimeError)):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)


def test_sharded_rejects_no_kernel():
    algo = make_algorithm("dana-zero", HP)
    cfg = ClusterConfig(num_workers=2, total_grads=10, mode="free",
                        shards=2, use_kernel=False)
    with pytest.raises(ValueError, match="flat kernel"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)


def test_fanout_pull_gathers_all_shards():
    """A pull-only message (grad None) through the FanoutMailbox comes
    back as the range-ordered tuple of shard view slices, equal to the
    single master's flat view."""
    algo = make_algorithm("dana-zero", HP)
    sm = ShardedMaster(algo, algo.init(PARAMS0, 3), shards=3,
                       history=History(), stop=threading.Event(),
                       total_grads=10, record_telemetry=False)
    stop = threading.Event()
    msg = GradMsg(0, None, None, 0, 0.0)
    assert sm.frontdoor.put(msg, stop)
    for srv in sm.shards_:
        (m,) = srv.mailbox.drain_nowait()
        srv._pull_reply(m)
    reply = msg.wait_reply(5.0)
    assert isinstance(reply.view, tuple) and len(reply.view) == 3
    single = Master(algo, algo.init(PARAMS0, 3), mailbox=Mailbox(),
                    history=History(), stop=threading.Event(),
                    total_grads=10, use_kernel=True,
                    record_telemetry=False)
    view, _ = single.initial_view(0)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(reply.view, axis=0)),
        np.asarray(view))


def test_fanout_mailbox_is_transparent_to_len():
    boxes = [Mailbox(), Mailbox()]
    front = FanoutMailbox(boxes)
    assert len(front) == 0
    stop = threading.Event()
    front.put(GradMsg(0, None, None, 0, 0.0), stop)
    assert len(front) == 1 and all(len(b) == 1 for b in boxes)
