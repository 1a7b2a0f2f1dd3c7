"""Scalar-prefetch memory tier: the three layers of the touched-slab
story.

Contracts:
  * kernel — ``flat_master_update_batch_prefetch`` (slab BlockSpec index
    maps driven by the scalar-prefetch schedule: 2u streams for u unique
    senders) is bit-exact against BOTH the jitted jnp reference and the
    full-slab ``_2d`` kernel for k in {1, 4, 8} with duplicated ids,
    across N in {2, 8, 64} — including the two-slab (sent-snapshot)
    shapes the full-slab budget could not tile at N = 64 — and its
    VMEM budget is a function of k, never N;
  * gap-aware — the prefetch two-phase lowering (one-row slab specs)
    matches the legacy grid and the jnp oracle across multiple row-tile
    revisits (two flushes of the same output block);
  * protocol — ``view_rows`` serves a pull view over only the declared
    rows, bit-equal to the full view's slice; ``_pull_reply`` echoes the
    honored range in ``Reply.rows`` (sent-family masters fall back to
    the full view — their send must refresh the snapshot slab row) and
    returns the served row count for the ``pull_rows`` counter;
  * placement — under skewed row ranges the busy_s-driven rebalancer
    moves at least one row range donor -> receiver and the final params
    stay bit-identical to the unrebalanced run (moving rows between
    shards changes WHERE work happens, never the math).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterConfig, Mailbox, Master, run_cluster
from repro.cluster.mailbox import GradMsg
from repro.core import FlatSpec, GammaModel, HyperParams, make_algorithm
from repro.core.metrics import History
from repro.data.synthetic import ClassificationTask
from repro.kernels.flat_update import FlatAlgorithm
from repro.kernels.flat_update.kernel import (
    _pick_block_rows, flat_master_update_batch_2d,
    flat_master_update_batch_gap, flat_master_update_batch_prefetch,
    gap_pallas_supported)
from repro.kernels.flat_update.ref import flat_master_update_batch_ref
from repro.models.toy import make_classifier_fns
from repro.obs.metrics import MetricsRegistry

HP = HyperParams(lr=0.05, momentum=0.9)
TASK = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
INIT, GRAD_FN, _ = make_classifier_fns([8, 16, 4])
PARAMS0 = INIT(jax.random.PRNGKey(0))


def _inputs(R=16, N=4, k=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    theta = jax.random.normal(ks[0], (R, 128))
    v = jax.random.normal(ks[1], (N, R, 128)) * 0.1
    v0 = jnp.sum(v, axis=0)
    u2 = jnp.abs(jax.random.normal(ks[2], (R, 128))) * 0.01
    sent = theta + 0.01 * jax.random.normal(ks[4], (N, R, 128))
    g = jax.random.normal(ks[3], (k, R, 128))
    # duplicated ids (momentum chaining through the VMEM window) mixed
    # with ids the batch never touches again
    ids = jnp.asarray([j % N for j in [0, 2, 0, 0, 1, 2, 0, 1]][:k],
                      jnp.int32)
    lrs = jnp.linspace(0.05, 0.03, k)
    lrs_next = jnp.linspace(0.049, 0.029, k)
    vscales = jnp.linspace(1.0, 0.8, k)
    scal = (lrs, lrs_next, jnp.full((k,), 0.9), jnp.ones((k,)), vscales)
    return theta, v, v0, u2, sent, g, ids, scal


# ---------------------------------------------------------------------------
# kernel: prefetch == full-slab == reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("n", [2, 8, 64])
def test_prefetch_matches_full_slab_and_ref(n, k):
    """The touched-slab kernel is a pure traffic optimization: state,
    views and v0 tracking are bit-exact against the full-slab kernel
    AND the jitted reference at every (N, k), duplicate ids included."""
    theta, v, v0, _, _, g, ids, scal = _inputs(N=n, k=k)
    lrs, lrs_next, gammas, cgs, vscales = scal
    args = (theta, v, v0, None, None, g, ids, lrs, lrs_next, gammas,
            cgs, vscales)
    out_p = flat_master_update_batch_prefetch(
        *args, nesterov=True, telemetry=True, interpret=True)
    out_2d = flat_master_update_batch_2d(
        *args, nesterov=True, telemetry=True, interpret=True)
    ref = jax.jit(lambda *a: flat_master_update_batch_ref(
        a[0], a[1], a[2], a[3], a[4], None, *a[5:], nesterov=True,
        telemetry=True))(*args)
    ref = ref[:5] + ref[6:]          # drop avg_step (gap-aware only)
    for o, f, r in zip(out_p, out_2d, ref):
        if o is None:
            assert f is None and r is None
            continue
        np.testing.assert_array_equal(np.asarray(o), np.asarray(f))
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


@pytest.mark.parametrize("k", [1, 4, 8])
def test_prefetch_two_slab_n64_regression(k):
    """N = 64 with the sent-snapshot slab: TWO (64, R, 128) slabs.  The
    full-slab budget window is 2N = 128 resident rows; the prefetch
    window is k + 2 regardless of N — this shape must pack, run, and
    stay bit-exact against the reference (and _2d where it still
    tiles)."""
    n = 64
    theta, v, v0, _, sent, g, ids, scal = _inputs(R=16, N=n, k=k)
    lrs, lrs_next, gammas, cgs, vscales = scal
    # the budget really is k-shaped: the prefetch window never grows
    # with N while the legacy window is the slab count itself
    assert _pick_block_rows(16, k + 2, 2) >= _pick_block_rows(16, n, 2)
    args = (theta, v, v0, None, sent, g, ids, lrs, lrs_next, gammas,
            cgs, vscales)
    out_p = flat_master_update_batch_prefetch(
        *args, nesterov=False, dc_lambda=2.0, sent_view=True,
        telemetry=False, interpret=True)
    out_2d = flat_master_update_batch_2d(
        *args, nesterov=False, dc_lambda=2.0, sent_view=True,
        telemetry=False, interpret=True)
    ref = jax.jit(lambda *a: flat_master_update_batch_ref(
        a[0], a[1], a[2], a[3], a[4], None, *a[5:], nesterov=False,
        dc_lambda=2.0, sent_view=True))(*args)
    ref = ref[:5] + ref[6:]
    for o, f, r in zip(out_p, out_2d, ref):
        if o is None:
            continue
        np.testing.assert_array_equal(np.asarray(o), np.asarray(f))
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


def test_prefetch_adaptive_tolerance_and_weighted_hat():
    """The two shapes that are NOT plain elementwise: the adaptive
    (Nadam) denominator fuses sqrt/divide differently across lowerings
    (1-ULP tolerance vs the ref, bit-exact vs _2d which shares the
    Pallas op order), and the weighted hat reduces the k-slot window
    (reduction-order tolerance)."""
    theta, v, v0, u2, _, g, ids, scal = _inputs(N=4, k=8)
    lrs, lrs_next, gammas, cgs, vscales = scal
    args = (theta, v, v0, u2, None, g, ids, lrs, lrs_next, gammas, cgs,
            vscales)
    out_p = flat_master_update_batch_prefetch(
        *args, nesterov=False, telemetry=False, interpret=True)
    out_2d = flat_master_update_batch_2d(
        *args, nesterov=False, telemetry=False, interpret=True)
    ref = jax.jit(lambda *a: flat_master_update_batch_ref(
        a[0], a[1], a[2], a[3], a[4], None, *a[5:],
        nesterov=False))(*args)
    ref = ref[:5] + ref[6:]
    for o, f, r in zip(out_p, out_2d, ref):
        if o is None:
            continue
        np.testing.assert_array_equal(np.asarray(o), np.asarray(f))
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-6, atol=2e-6)
    # weighted hat (dana-hetero): base + windowed delta decomposition
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (8, 4))) + 0.1
    args_w = (theta, v, None, None, None, g, ids, lrs, lrs_next, gammas,
              cgs, vscales)
    out_pw = flat_master_update_batch_prefetch(
        *args_w, nesterov=False, hat_mode="weighted", weights=w,
        telemetry=False, interpret=True)
    out_2w = flat_master_update_batch_2d(
        *args_w, nesterov=False, hat_mode="weighted", weights=w,
        telemetry=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pw[0]),
                                  np.asarray(out_2w[0]))
    np.testing.assert_array_equal(np.asarray(out_pw[1]),
                                  np.asarray(out_2w[1]))
    np.testing.assert_allclose(np.asarray(out_pw[5]),
                               np.asarray(out_2w[5]),
                               rtol=2e-6, atol=2e-6)


def test_prefetch_equals_sequential_chaining():
    """ONE k-message prefetch call == k sequential 1-message calls with
    duplicate ids: the VMEM window chain (not HBM round trips) carries
    worker momentum between a worker's messages."""
    k = 8
    theta, v, v0, _, _, g, ids, scal = _inputs(N=3, k=k)
    ids = jnp.asarray([0, 2, 0, 0, 1, 2, 0, 1], jnp.int32)
    lrs, lrs_next, gammas, cgs, vscales = scal
    batch = flat_master_update_batch_prefetch(
        theta, v, v0, None, None, g, ids, lrs, lrs_next, gammas, cgs,
        vscales, nesterov=False, telemetry=False, interpret=True)
    th_s, v_s, v0_s = theta, v, v0
    for j in range(k):
        th_s, v_s, v0_s, _, _, _, _ = flat_master_update_batch_prefetch(
            th_s, v_s, v0_s, None, None, g[j:j + 1], ids[j:j + 1],
            lrs[j:j + 1], lrs_next[j:j + 1], gammas[j:j + 1],
            cgs[j:j + 1], vscales[j:j + 1], nesterov=False,
            telemetry=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(batch[0]), np.asarray(th_s))
    np.testing.assert_array_equal(np.asarray(batch[1]), np.asarray(v_s))
    np.testing.assert_array_equal(np.asarray(batch[2]), np.asarray(v0_s))


def test_prefetch_untouched_slab_rows_survive():
    """The 2u-stream contract's correctness half: slab rows of workers
    the batch never mentions must come through IDENTICAL (their output
    blocks alias their input blocks; no schedule entry writes them)."""
    n, k = 8, 4
    theta, v, v0, _, _, g, _, scal = _inputs(N=n, k=k)
    ids = jnp.asarray([1, 5, 1, 5], jnp.int32)      # u = 2 of N = 8
    lrs, lrs_next, gammas, cgs, vscales = scal
    out = flat_master_update_batch_prefetch(
        theta, v, v0, None, None, g, ids, lrs, lrs_next, gammas, cgs,
        vscales, nesterov=False, telemetry=False, interpret=True)
    v_new = np.asarray(out[1])
    for i in range(n):
        if i in (1, 5):
            assert not np.array_equal(v_new[i], np.asarray(v[i]))
        else:
            np.testing.assert_array_equal(v_new[i], np.asarray(v[i]))


# ---------------------------------------------------------------------------
# gap-aware prefetch: two-phase lowering, multiple row-tile revisits
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 4])
def test_gap_prefetch_matches_legacy_and_ref(k):
    """The gap-aware prefetch variant (one-row slab specs, budget
    independent of N) over a state spanning several row tiles: both
    flushes of every output block land, duplicate ids chain, and the
    result tracks the legacy full-slab grid and the jnp oracle to
    reduction-order tolerance."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    R, N = 512, 3
    theta = jax.random.normal(ks[0], (R, 128))
    v = jax.random.normal(ks[1], (N, R, 128)) * 0.1
    sent = theta + 0.01 * jax.random.normal(ks[2], (N, R, 128))
    g = jax.random.normal(ks[3], (k, R, 128))
    ids = jnp.asarray([0, 2, 0, 1][:k], jnp.int32)
    lrs = jnp.linspace(0.05, 0.04, k)
    gammas = jnp.full((k,), 0.9)
    cgs = jnp.ones((k,))
    vscales = jnp.linspace(1.0, 0.9, k)
    avg = jnp.float32(1e-3)
    assert gap_pallas_supported(R, N, prefetch=True)
    outs = {}
    for pf in (True, False):
        outs[pf] = flat_master_update_batch_gap(
            theta, v, sent, avg, g, ids, lrs, gammas, cgs, vscales,
            gap_ema=0.99, n_elems=R * 128, telemetry=True,
            interpret=True, prefetch=pf)
    outr = jax.jit(lambda: flat_master_update_batch_ref(
        theta, v, None, None, sent, avg, g, ids, lrs, lrs, gammas, cgs,
        vscales, nesterov=False, gap_aware=True, gap_ema=0.99,
        n_elems=R * 128, hat_mode="theta", telemetry=True))()
    ref_pairs = [(0, 0), (1, 1), (2, 4), (4, 6), (5, 7)]
    for a, b in ref_pairs:
        np.testing.assert_allclose(np.asarray(outs[True][a]),
                                   np.asarray(outr[b]),
                                   rtol=2e-6, atol=2e-7)
        np.testing.assert_allclose(np.asarray(outs[True][a]),
                                   np.asarray(outs[False][a]),
                                   rtol=2e-6, atol=2e-7)
    np.testing.assert_allclose(float(outs[True][3]), float(outr[5]),
                               rtol=2e-6)


def test_prefetch_pays_routing_rule():
    """The memory-tier dispatch: dense full-slab while the whole slab
    rides one tile (2N streams are one sequential burst there),
    scalar-prefetch once the dense window shrinks the tiles or cannot
    tile at all."""
    from repro.kernels.flat_update import prefetch_pays
    assert not prefetch_pays(256, 8, 8)      # dense tiles survive
    assert not prefetch_pays(256, 32, 8)
    assert prefetch_pays(256, 64, 8)         # dense tiles shrink
    assert prefetch_pays(256, 2048, 8)       # dense cannot tile at all
    assert prefetch_pays(256, 64, 8, n_slabs=2)
    assert prefetch_pays(512, 64, 4, gap=True)
    # k so large even the prefetch window cannot tile: the dispatch
    # falls back rather than lowering an unloadable kernel
    assert not prefetch_pays(256, 8, 4096)


def test_gap_prefetch_budget_independent_of_n():
    """gap_pallas_supported: the legacy grid cannot tile two 64-worker
    slabs over a small state, the prefetch grid can (its window is 3
    rows, period)."""
    assert gap_pallas_supported(512, 64, prefetch=True)
    assert _pick_block_rows(512, 3, 2) >= _pick_block_rows(512, 64, 2)


# ---------------------------------------------------------------------------
# protocol: hot-row pulls
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["dana-zero", "lwp", "dana-hetero",
                                  "dana-nadam", "asgd"])
def test_view_rows_matches_full_view_slice(name):
    """view_rows is a pure row slice of the send view (row-local
    reduction): bit-equal to the full view's [r0:r1] for every
    non-sent family, empty ranges give a (0, lanes) buffer."""
    algo = make_algorithm(name, HP)
    fa = FlatAlgorithm(algo)
    flat = fa.init(PARAMS0, 4)
    full = fa._view_flat(flat, jnp.int32(1))
    for r0, r1 in ((0, 8), (8, 16), (0, int(full.shape[-2]))):
        part = fa.view_rows(flat, jnp.int32(1), r0, r1)
        np.testing.assert_array_equal(np.asarray(full[r0:r1]),
                                      np.asarray(part))
    assert fa.view_rows(flat, jnp.int32(1), 8, 8).shape == \
        (0, full.shape[-1])


def _pull_master(name):
    algo = make_algorithm(name, HP)
    state = algo.init(PARAMS0, 3)
    return Master(algo, state, mailbox=Mailbox(), history=History(),
                  stop=threading.Event(), total_grads=10,
                  record_telemetry=False, use_kernel=True)


def test_master_pull_reply_serves_hot_rows():
    """A pull with a declared row range gets a partial view: Reply.rows
    echoes the honored range, the view is the full view's slice, and
    the served row count (the pull_rows counter feed) is the range."""
    m = _pull_master("dana-zero")
    full, _ = m.initial_view(1)
    msg = GradMsg(1, None, None, 0, 0.0, rows=(0, 8))
    served = m._pull_reply(msg)
    reply = msg.wait_reply(1.0)
    assert served == 8 and reply.rows == (0, 8)
    np.testing.assert_array_equal(np.asarray(reply.view),
                                  np.asarray(full)[0:8])


def test_master_pull_reply_sent_family_full_fallback():
    """Sent-snapshot masters must refresh the worker's whole snapshot
    slab row on send — a hot-row request falls back to the full view
    (Reply.rows None -> the worker replaces, never merges)."""
    m = _pull_master("dc-asgd")
    rows = int(m._flat_state["theta"].shape[-2])
    msg = GradMsg(1, None, None, 0, 0.0, rows=(0, 8))
    served = m._pull_reply(msg)
    reply = msg.wait_reply(1.0)
    assert reply.rows is None and served == rows
    assert reply.view.shape[-2] == rows


def test_cluster_hot_row_pulls_with_dropout():
    """End to end, free mode: dropped-out workers rejoin through a
    pull-only request carrying their hot range; the run completes with
    every gradient applied for single and sharded masters, and the
    serve loop's memory-tier counters observe u <= N slab traffic."""
    for shards in (1, 2):
        from repro.cluster.faults import FaultPlan
        algo = make_algorithm("dana-zero", HP)
        reg = MetricsRegistry()
        cfg = ClusterConfig(
            num_workers=4, total_grads=160, eval_every=10_000,
            mode="free", coalesce=2, exec_model=GammaModel(seed=5),
            shards=shards, faults=FaultPlan(dropout=((1, 20, 40),)),
            hot_rows=(None, (0, 8), (0, 8), None))
        stats = {}
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg,
                    stats_out=stats, metrics=reg)
        assert stats["applied"] == 160
        snap = reg.snapshot()
        streamed = snap["slab_rows_streamed"]["value"]
        total = snap["slab_rows_total"]["value"]
        assert 0 < streamed <= total


# ---------------------------------------------------------------------------
# placement: busy_s-driven row rebalancing
# ---------------------------------------------------------------------------
def test_rebalance_moves_rows_and_preserves_math(monkeypatch):
    """Two shards with deliberately skewed ranges (1272 vs 8 of the 1280
    rows of a [256, 512, 4] model): the watermark rebalancer must move at
    least one row range from the overloaded shard, and the final params must
    be bit-identical to the same run with rebalancing off — placement
    changes where rows live, never what they compute.

    The busy signal is pinned to rows-held-per-shard: on this CPU the
    per-message cost is dispatch-dominated, so the real wall-clock
    ``busy_s`` gap between a 1272-row and an 8-row shard is small
    enough that suite-level machine load can flip the threshold — the
    decision input is deterministic here, every layer downstream of it
    (watermark plan cache, rendezvous, slice/merge handoff, moving wire
    format) runs for real."""
    from repro.cluster.sharded import RowRebalancer
    monkeypatch.setattr(
        RowRebalancer, "_busy",
        lambda self: [float(s.r1 - s.r0) for s in self.owner.shards_])
    task = ClassificationTask(dim=256, num_classes=4, batch_size=8,
                              seed=3)
    init, grad_fn, _ = make_classifier_fns([256, 512, 4])
    params0 = init(jax.random.PRNGKey(0))
    rows = FlatSpec.from_tree(params0).rows          # 1280: 5 full tiles
    cut = rows - 8

    def run(rebalance):
        algo = make_algorithm("dana-zero", HP)
        cfg = ClusterConfig(
            num_workers=4, total_grads=40, eval_every=10,
            mode="deterministic", coalesce=1, exec_model=GammaModel(seed=5),
            shards=2, record_telemetry=False,
            shard_ranges=((0, cut), (cut, rows)),
            rebalance=rebalance, rebalance_threshold=1.05)
        stats = {}
        hist = run_cluster(algo, grad_fn, params0, task.batch, cfg,
                           stats_out=stats)
        return hist.final_params, stats

    p_no, _ = run(False)
    p_rb, s_rb = run(True)
    moves = s_rb["rebalance_moves"]
    assert moves, "rebalancer made no moves under heavy skew"
    for wm, donor, recv, n_rows in moves:
        assert donor == 0 and recv == 1 and n_rows % 8 == 0 and n_rows > 0
    r0, r1 = s_rb["shard_ranges"][0]
    assert (r1 - r0) < cut                  # shard 0 really shrank
    for a, b in zip(jax.tree.leaves(p_no), jax.tree.leaves(p_rb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rebalance_config_guards():
    """Gap-aware (cross-shard norm exchange) and telemetry (views are
    sliced to static ranges) are incompatible with moving ranges —
    explicit errors, not silent corruption."""
    with pytest.raises(ValueError, match="rebalance"):
        run_cluster(make_algorithm("dana-zero", HP), GRAD_FN, PARAMS0,
                    TASK.batch,
                    ClusterConfig(num_workers=2, total_grads=10,
                                  shards=1, rebalance=True))
    task_cfg = dict(num_workers=2, total_grads=10, shards=2,
                    coalesce=1, mode="deterministic",
                    exec_model=GammaModel(seed=1))
    algo = make_algorithm("ga-asgd", HP)
    with pytest.raises(ValueError, match="gap"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                    ClusterConfig(rebalance=True,
                                  record_telemetry=False, **task_cfg))
    algo = make_algorithm("dana-zero", HP)
    with pytest.raises(ValueError, match="telemetry"):
        run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                    ClusterConfig(rebalance=True,
                                  record_telemetry=True, **task_cfg))


def test_custom_shard_ranges_validated():
    base = dict(num_workers=2, total_grads=10, shards=2, coalesce=1,
                mode="deterministic", exec_model=GammaModel(seed=1),
                record_telemetry=False)
    algo = make_algorithm("dana-zero", HP)
    for bad in (((0, 8),),                       # wrong count
                ((0, 8), (16, 24)),              # gap
                ((0, 24), (8, 24))):             # overlap / disorder
        with pytest.raises(ValueError):
            run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch,
                        ClusterConfig(shard_ranges=bad, **base))
