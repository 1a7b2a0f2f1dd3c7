"""Flat-state master path: pack/unpack layout, the batched k-message
kernel, and the load-bearing equivalences.

Contracts:
  * FlatSpec round-trips arbitrary pytrees (incl. stacked per-worker
    state) through the (R, 128) layout;
  * the batched Pallas kernel (interpret mode here) equals the jnp
    reference — incl. the sent-snapshot slab, delay compensation, and
    per-message schedule scalars — and ONE k-message call equals k
    sequential 1-message calls for mixed/duplicated worker ids;
  * the master's flat fused pass and the tree fused pass both match the
    op-by-op reference for EVERY kernel-eligible algorithm in the
    registry, moving lr schedules included — to compile-rounding ulps
    for the elementwise family (separately compiled programs round
    differently), to reduction-order tolerance for gap-aware (its
    penalty is a norm over the flat buffer instead of leaf-by-leaf);
  * the engine's flat and tree executions both reproduce the op-by-op
    engine run;
  * ``eligibility_matrix`` — the documented flat/shard/schedule
    eligibility contract — cannot silently regress.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import Mailbox, Master
from repro.core import (HyperParams, REGISTRY, Schedule, SimulationConfig,
                        make_algorithm, run_simulation)
from repro.core.flat import FlatSpec
from repro.core.metrics import History
from repro.data.synthetic import ClassificationTask
from repro.kernels.flat_update import (FLAT_ELIGIBLE, SEND_KERNEL,
                                       SENT_STEP, FlatAlgorithm,
                                       eligibility_matrix, family_spec_for,
                                       flat_send_view, flat_send_view_ref,
                                       kernel_eligible, send_spec_for,
                                       shard_bitexact)
from repro.kernels.flat_update.kernel import (flat_master_update_batch_2d,
                                              flat_master_update_batch_gap)
from repro.kernels.flat_update.ref import flat_master_update_batch_ref
from repro.models.toy import make_classifier_fns

HP = HyperParams(lr=0.05, momentum=0.9)
TASK = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
INIT, GRAD_FN, _ = make_classifier_fns([8, 16, 4])
PARAMS0 = INIT(jax.random.PRNGKey(0))

ELIGIBLE = sorted(n for n in REGISTRY
                  if kernel_eligible(make_algorithm(n, HP)))
# a decidedly non-constant schedule: warm-up ramp + two decay steps
# land inside the short test runs, so lr(t), lr(t+1) and the momentum
# -correction rescale all move while the equivalences must hold
SCHED = Schedule(base_lr=0.05, num_workers=4, warmup_steps=6,
                 milestones=(5, 9), decay_factor=0.5)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_trees_close(a, b, tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=tol, atol=tol)


# Two separately compiled XLA programs do not round alike: the CPU
# backend contracts a*b + c into one FMA in some programs and not in
# others, so the tree pass, the flat pass and an op-by-op reference may
# each differ by an ulp or so per f32 operation.  A contract that spans
# two compilations therefore compares each side with the op-by-op
# reference, to a few ulps of the reference's largest magnitude:
CHAIN_ULPS = 8        # one k <= 8 message batch, ~10 f32 ops per element
ENGINE_ULPS = 64      # 60 engine steps; each step's gradient is taken at
#                       the previous steps' params, so the ulps compound
F32_EPS = float(np.finfo(np.float32).eps)


def _assert_close_to_ref(got, ref, ulps, tol=0.0):
    """``got`` matches the op-by-op reference to ``ulps`` f32 ulps of
    the reference's largest magnitude, or to ``tol`` where a member's
    own reduction order already sets a wider one."""
    for x, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref),
                    strict=True):
        r = np.asarray(r, np.float32)
        scale = float(np.max(np.abs(r))) if r.size else 0.0
        np.testing.assert_allclose(
            np.asarray(x, np.float32), r,
            rtol=max(tol, ulps * F32_EPS),
            atol=max(tol, ulps * F32_EPS * scale))


def _eager_reference(name, n, ids_l, grads, nows_l=None, schedule=None):
    """The plain reference: the algorithm's own receive->send applied
    message by message, op by op (no jit, so no fusion)."""
    algo = make_algorithm(name, HP, schedule)
    views = []
    with jax.disable_jit():
        state = algo.init(PARAMS0, n)
        for j, i in enumerate(ids_l):
            now = 0.0 if nows_l is None else nows_l[j]
            state, view = algo.receive_send(state, jnp.int32(i), grads[j],
                                            jnp.float32(now))
            views.append(view)
    return state, views


# ---------------------------------------------------------------------------
# FlatSpec layout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shapes", [
    {"a": (17,), "b": (3, 5)},
    {"w1": (32, 64), "b1": (64,), "w2": (64, 10), "b2": (10,)},
    {"x": (1,)},
])
def test_flat_spec_roundtrip(shapes):
    key = jax.random.PRNGKey(0)
    tree = {k: jax.random.normal(jax.random.fold_in(key, j), s)
            for j, (k, s) in enumerate(shapes.items())}
    spec = FlatSpec.from_tree(tree)
    assert spec.rows % 8 == 0 and spec.rows * 128 >= spec.n_elems
    _assert_trees_equal(tree, spec.unpack(spec.pack(tree)))
    stacked = jax.tree.map(lambda l: jnp.stack([l, 2 * l, -l]), tree)
    _assert_trees_equal(stacked,
                        spec.unpack_stacked(spec.pack_stacked(stacked)))


def test_flat_spec_pads_with_zeros():
    tree = {"a": jnp.ones((5,))}
    buf = FlatSpec.from_tree(tree).pack(tree)
    flat = np.asarray(buf).reshape(-1)
    assert flat[:5].sum() == 5.0 and flat[5:].sum() == 0.0


def test_eligible_set_is_the_flat_family():
    assert ELIGIBLE == sorted(FLAT_ELIGIBLE) == [
        "asgd", "dana-dc", "dana-hetero", "dana-nadam", "dana-slim",
        "dana-zero", "dc-asgd", "ga-asgd", "lwp", "multi-asgd",
        "nadam-asgd", "nag-asgd", "sa-asgd"]
    # the matrix is CLOSED over the asynchronous registry: only the
    # elastic-replica pair (whose sends are per-worker replicas, not a
    # master-state view), yellowfin's closed-loop autotuner, and the
    # synchronous baseline stay on the tree path
    for name in ("easgd", "dana-easgd", "yellowfin", "ssgd"):
        assert not kernel_eligible(make_algorithm(name, HP)), name


def test_eligibility_matrix_contract():
    """The documented eligibility matrix (README Performance section).
    CI fails here — and in the bench smoke — if an algorithm silently
    drops out of (or into) the flat/send/shard/schedule paths."""
    m = eligibility_matrix()
    assert set(m) == set(REGISTRY)
    assert sorted(n for n in m if m[n]["flat"]) == sorted(FLAT_ELIGIBLE)
    # the send_kernel column: look-ahead senders run the weighted-slab
    # reduction kernel; everyone else sends theta itself
    assert sorted(n for n in m if m[n]["send_kernel"]) \
        == sorted(SEND_KERNEL)
    for name in FLAT_ELIGIBLE:
        assert m[name]["schedule"], name     # moving lr supported
        assert m[name]["shard"], name        # row-sharded master runs it
        # bit-exact sharding for the elementwise family (the hetero
        # weighted send is per row, so it shards bit-exactly too);
        # gap-aware sums per-shard norm partials (tolerance only)
        assert m[name]["shard_bitexact"] == (name != "ga-asgd"), name
        assert shard_bitexact(make_algorithm(name, HP)) \
            == m[name]["shard_bitexact"]
        spec = send_spec_for(make_algorithm(name, HP))
        assert m[name]["send_kernel"] == (spec.source is not None), name
    for name in set(REGISTRY) - set(FLAT_ELIGIBLE):
        assert not any(m[name].values()), name


# ---------------------------------------------------------------------------
# batched kernel vs reference / vs sequential
# ---------------------------------------------------------------------------
def _flat_inputs(R=16, N=4, k=8, seed=0, moving_lr=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    theta = jax.random.normal(ks[0], (R, 128))
    v = jax.random.normal(ks[1], (N, R, 128)) * 0.1
    v0 = jnp.sum(v, axis=0)
    u2 = jnp.abs(jax.random.normal(ks[2], (R, 128))) * 0.01
    sent = theta + 0.01 * jax.random.normal(ks[4], (N, R, 128))
    g = jax.random.normal(ks[3], (k, R, 128))
    ids = jnp.asarray([j * 5 % N for j in range(k)], jnp.int32)
    if moving_lr:
        lrs = jnp.linspace(0.05, 0.03, k)
        lrs_next = jnp.linspace(0.049, 0.029, k)
        vscales = jnp.linspace(1.0, 0.8, k)
    else:
        lrs = lrs_next = jnp.full((k,), 0.05)
        vscales = jnp.ones((k,))
    scal = (lrs, lrs_next, jnp.full((k,), 0.9), jnp.ones((k,)), vscales)
    return theta, v, v0, u2, sent, g, ids, scal


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("track_v0", [False, True])
@pytest.mark.parametrize("adaptive", [False, True])
@pytest.mark.parametrize("moving_lr", [False, True])
def test_batched_kernel_matches_ref(nesterov, track_v0, adaptive,
                                    moving_lr):
    theta, v, v0, u2, _, g, ids, scal = _flat_inputs(moving_lr=moving_lr)
    lrs, lrs_next, gammas, cgs, vscales = scal
    args = (theta, v, v0 if track_v0 else None, u2 if adaptive else None,
            None, g, ids, lrs, lrs_next, gammas, cgs, vscales)
    outs = flat_master_update_batch_2d(*args, nesterov=nesterov,
                                       telemetry=True, interpret=True)
    ref = jax.jit(lambda *a: flat_master_update_batch_ref(
        a[0], a[1], a[2], a[3], a[4], None, *a[5:], nesterov=nesterov,
        telemetry=True))(*args)
    ref = ref[:5] + ref[6:]          # drop avg_step (gap-aware only)
    # sqrt/divide (adaptive) fuses differently under the two lowerings;
    # the momentum family is elementwise mul/add and stays bit-exact
    tol = 2e-6 if adaptive else 0.0
    for o, r in zip(outs, ref):
        if o is None:
            assert r is None
            continue
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("sent_view", [False, True])
@pytest.mark.parametrize("track_v0", [False, True])
def test_batched_kernel_matches_ref_sent_slab(track_v0, sent_view):
    """The sent-snapshot slab + delay compensation (the dc-asgd /
    dana-dc shapes) is elementwise: Pallas == reference bit-for-bit,
    moving schedule scalars included."""
    theta, v, v0, _, sent, g, ids, scal = _flat_inputs(moving_lr=True)
    lrs, lrs_next, gammas, cgs, vscales = scal
    args = (theta, v, v0 if track_v0 else None, None, sent, g, ids, lrs,
            lrs_next, gammas, cgs, vscales)
    outs = flat_master_update_batch_2d(*args, nesterov=False,
                                       dc_lambda=2.0, sent_view=sent_view,
                                       telemetry=True, interpret=True)
    ref = jax.jit(lambda *a: flat_master_update_batch_ref(
        a[0], a[1], a[2], a[3], a[4], None, *a[5:], nesterov=False,
        dc_lambda=2.0, sent_view=sent_view, telemetry=True))(*args)
    ref = ref[:5] + ref[6:]
    for o, r in zip(outs, ref):
        if o is None:
            assert r is None
            continue
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("with_sent", [False, True])
def test_batched_kernel_equals_sequential(k, with_sent):
    """ONE k-message pallas_call == k sequential 1-message calls, with
    duplicated worker ids inside the batch (momentum chaining; with the
    sent slab, message j+1 must see j's refreshed snapshot)."""
    theta, v, v0, _, sent, g, ids, scal = _flat_inputs(k=k, N=3)
    lrs, lrs_next, gammas, cgs, vscales = scal
    sent = sent[:3] if with_sent else None
    lam = 2.0 if with_sent else None
    ids = jnp.asarray([0, 2, 0, 0, 1, 2, 0, 1][:k], jnp.int32)
    batch = flat_master_update_batch_2d(
        theta, v, v0, None, sent, g, ids, lrs, lrs_next, gammas, cgs,
        vscales, nesterov=False, dc_lambda=lam, sent_view=with_sent,
        telemetry=False, interpret=True)
    th_s, v_s, v0_s, sent_s = theta, v, v0, sent
    hats = []
    for j in range(k):
        th_s, v_s, v0_s, _, sent_s, hat, _ = flat_master_update_batch_2d(
            th_s, v_s, v0_s, None, sent_s, g[j:j + 1], ids[j:j + 1],
            lrs[j:j + 1], lrs_next[j:j + 1], gammas[j:j + 1],
            cgs[j:j + 1], vscales[j:j + 1], nesterov=False,
            dc_lambda=lam, sent_view=with_sent, telemetry=False,
            interpret=True)
        hats.append(hat[0])
    np.testing.assert_array_equal(np.asarray(batch[0]), np.asarray(th_s))
    np.testing.assert_array_equal(np.asarray(batch[1]), np.asarray(v_s))
    np.testing.assert_array_equal(np.asarray(batch[2]), np.asarray(v0_s))
    if with_sent:
        np.testing.assert_array_equal(np.asarray(batch[4]),
                                      np.asarray(sent_s))
    for j in range(k):
        np.testing.assert_array_equal(np.asarray(batch[5][j]),
                                      np.asarray(hats[j]))


def test_batched_kernel_multi_row_tiles():
    """Rows spanning several grid tiles: state revisiting across the
    message axis must carry updates tile-locally."""
    theta, v, v0, _, _, g, ids, scal = _flat_inputs(R=512, N=2, k=3)
    lrs, lrs_next, gammas, cgs, vscales = scal
    args = (theta, v, v0, None, None, g, ids, lrs, lrs_next, gammas,
            cgs, vscales)
    out_k = flat_master_update_batch_2d(*args, nesterov=True,
                                        telemetry=False, interpret=True)
    out_r = jax.jit(lambda *a: flat_master_update_batch_ref(
        a[0], a[1], a[2], a[3], a[4], None, *a[5:],
        nesterov=True))(*args)
    for o, r in zip(out_k[:3], out_r[:3]):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


# ---------------------------------------------------------------------------
# master: flat fused pass == tree fused pass, every eligible algorithm
# ---------------------------------------------------------------------------
def _masters(name, n, schedule=None, **kw):
    algo = make_algorithm(name, HP, schedule)
    state = algo.init(PARAMS0, n)
    master = Master(algo, state, mailbox=Mailbox(), history=History(),
                    stop=threading.Event(), total_grads=100, coalesce=8,
                    record_telemetry=False, **kw)
    return algo, state, master


def _grads(k, seed=0):
    return tuple(jax.jit(GRAD_FN)(PARAMS0, TASK.batch(j % 3, seed + j))
                 for j in range(k))


def _fused_tol(name):
    # dana-nadam / nadam-asgd: sqrt/divide fuses differently across
    # lowerings.
    # nag-asgd: the shared-momentum N=1 slab makes XLA fuse the batched
    # chain with different FMA contraction than the per-message tree loop
    # — 1-ULP noise, semantics identical (k=1 is bit-exact, tested below).
    # ga-asgd: the gap penalty reduces over the flat buffer instead of
    # leaf-by-leaf; dana-hetero's rate-weighted view reduces the N-way
    # mix over flat rows (state stays bit-exact, views are tolerance).
    return 2e-6 if name in ("dana-nadam", "nadam-asgd", "nag-asgd",
                            "ga-asgd", "dana-hetero") else 0.0


def _fam_keys(algo):
    fam = family_spec_for(algo)
    return (["theta0"]
            + ([fam.momentum_key] if fam.momentum_key else [])
            + ([fam.sum_key] if fam.sum_key else [])
            + ([fam.u2_key] if fam.u2_key else [])
            + ([fam.sent_key] if fam.sent_key else [])
            + (["sent_t"] if fam.staleness_lr else [])
            + (["interval", "last_t"] if fam.rate_weighted else [])
            + (["avg_step"] if fam.gap_aware else []))


def _check_flat_vs_tree(name, ids_l, schedule=None, k_batch=None,
                        nows_l=None):
    """Drive the SAME message sequence through the tree master's fused
    pass and the flat master's batched kernel; compare each side's state
    and views with the op-by-op reference (``_eager_reference``).
    ``nows_l`` feeds per-message timestamps (dana-hetero's rate lane)."""
    n = 4
    _, state, m_tree = _masters(name, n, schedule)
    algo_f, _, m_flat = _masters(name, n, schedule, use_kernel=True)
    assert m_flat.state_is_flat
    spec = m_flat._flat_algo.spec
    grads = _grads(len(ids_l), seed=11)
    k_batch = k_batch or len(ids_l)
    s_t, s_f = state, m_flat._flat_state
    v_t, v_f = [], []
    for off in range(0, len(ids_l), k_batch):
        ids = jnp.asarray(ids_l[off:off + k_batch], jnp.int32)
        k = len(ids)
        nows = (jnp.asarray(nows_l[off:off + k], jnp.float32)
                if nows_l is not None else jnp.zeros((k,), jnp.float32))
        chunk = grads[off:off + k]
        s_t, vt, _, _ = m_tree._get_fused(k, False)(s_t, ids, nows,
                                                    chunk, None)
        s_f, vf, _, _, _ = m_flat._get_fused_flat(k, False)(
            s_f, ids, nows, tuple(spec.pack(g) for g in chunk), None)
        v_t.extend(vt)
        v_f.extend(spec.unpack(v) for v in vf)
    tree_f = m_flat._flat_algo.tree_state(s_f)
    s_r, v_r = _eager_reference(name, n, ids_l, grads, nows_l, schedule)
    tol = _fused_tol(name)
    # dana-hetero: the STATE is elementwise (the weighted mix only
    # shapes the reply views); its views carry the reduction tolerance
    state_tol = 0.0 if name == "dana-hetero" else tol
    for key in _fam_keys(algo_f):
        for got in (s_t, tree_f):
            _assert_close_to_ref(got[key], s_r[key], CHAIN_ULPS, state_tol)
    for a, b, r in zip(v_t, v_f, v_r, strict=True):
        _assert_close_to_ref(a, r, CHAIN_ULPS, tol)
        _assert_close_to_ref(b, r, CHAIN_ULPS, tol)


@pytest.mark.parametrize("name", ELIGIBLE)
def test_flat_fused_matches_tree_fused(name):
    """The one-kernel flat batch must reproduce the generic tree fused
    pass (bit-for-bit for the elementwise family) for every eligible
    algorithm, duplicate worker ids included."""
    _check_flat_vs_tree(name, [1, 3, 1, 0])


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("name", ["dc-asgd", "dana-dc", "ga-asgd"])
def test_sent_family_flat_matches_tree_batched(name, k):
    """The newly eligible sent-snapshot family: flat == tree across
    batch sizes k in {1, 4, 8} with duplicated worker ids (message j+1
    must see j's refreshed snapshot inside ONE kernel call)."""
    _check_flat_vs_tree(name, [1, 3, 1, 0, 2, 1, 3, 3], k_batch=k)


@pytest.mark.parametrize("k", [1, 4, 8])
def test_hetero_flat_matches_tree_batched(k):
    """dana-hetero (rate-weighted look-ahead) on the flat path: the rate
    lane advances from real per-message timestamps exactly like the tree
    path's receive(now=...), duplicate ids chain through their own
    interval updates, and the weighted views agree to reduction-order
    tolerance (state bit-exact) across batch sizes k in {1, 4, 8}."""
    _check_flat_vs_tree("dana-hetero", [1, 3, 1, 0, 2, 1, 3, 3],
                        k_batch=k,
                        nows_l=[0.4, 0.9, 1.0, 1.7, 2.1, 2.2, 3.0, 3.8])


@pytest.mark.parametrize("name", ["asgd", "lwp"])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_momentum_free_and_lwp_flat_bit_exact(name, k):
    """The newly eligible asgd (gamma = 0 family update) and lwp
    (shared momentum + tau look-ahead, hat mode "self") are elementwise:
    flat and tree both match the op-by-op reference to compile-rounding
    ulps at every batch size."""
    _check_flat_vs_tree(name, [1, 3, 1, 0, 2, 1, 3, 3], k_batch=k)


@pytest.mark.parametrize("name", ["dana-zero", "dc-asgd", "multi-asgd",
                                  "dana-nadam"])
def test_scheduled_flat_matches_tree_fused(name):
    """Moving lr schedule (warm-up ramp + decay milestones inside the
    run): the flat path's per-message lr(t)/lr(t+1) + lazy vscale feed
    must reproduce the tree path — both match the op-by-op reference to
    compile-rounding ulps for the elementwise family.  This is the
    lifted constant-lr restriction."""
    _check_flat_vs_tree(name, [1, 3, 1, 0, 2, 1, 3, 3], schedule=SCHED,
                        k_batch=4)


def test_flat_fused_telemetry_matches_tree():
    """gaps/grad-norms from the flat pass equal the tree pass (reduction
    order differs -> allclose, not bitwise)."""
    k = 4
    _, state, m_tree = _masters("dana-zero", 4)
    _, _, m_flat = _masters("dana-zero", 4, use_kernel=True)
    ids = jnp.asarray([0, 2, 2, 1], jnp.int32)
    nows = jnp.zeros((k,), jnp.float32)
    grads = _grads(k, seed=3)
    views = tuple(jax.tree.map(lambda l: l + 0.01 * j, PARAMS0)
                  for j in range(k))
    spec = m_flat._flat_algo.spec
    _, _, gaps_t, gn_t, _ = m_tree._get_fused(k, True)(state, ids, nows,
                                                       grads, views)
    _, _, gaps_f, gn_f, _, _ = m_flat._get_fused_flat(k, True)(
        m_flat._flat_state, ids, nows,
        tuple(spec.pack(g) for g in grads),
        tuple(spec.pack(v) for v in views))
    np.testing.assert_allclose(np.asarray(gaps_f), np.asarray(gaps_t),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(gn_f), np.asarray(gn_t),
                               rtol=1e-5, atol=1e-7)


def test_flat_master_pull_and_state_roundtrip():
    """initial_view (flat wire format) and the state property agree with
    the tree master."""
    _, state, m_tree = _masters("dana-zero", 3)
    _, _, m_flat = _masters("dana-zero", 3, use_kernel=True)
    vt, _ = m_tree.initial_view(0)
    vf, _ = m_flat.initial_view(0)
    _assert_trees_equal(vt, m_flat._flat_algo.spec.unpack(vf))
    _assert_trees_equal(m_tree.state["theta0"], m_flat.state["theta0"])
    _assert_trees_equal(m_tree.master_params(), m_flat.master_params())


def test_flat_accepts_moving_schedule():
    """The constant-lr restriction is lifted: FlatAlgorithm executes any
    schedule (vectorized for the standard ``Schedule``, per-step calls
    for custom callables) and keeps vscale on the tree path's exact
    correction sequence."""
    algo = make_algorithm("dana-slim", HP, SCHED)
    fa = FlatAlgorithm(algo)
    flat = fa.init(PARAMS0, 3)
    for j, i in enumerate([0, 2, 1, 1]):
        flat, _ = fa.receive_send(flat, jnp.int32(i),
                                  _grads(1, seed=j)[0])
    ref = make_algorithm("dana-slim", HP, SCHED)
    st = ref.init(PARAMS0, 3)
    for j, i in enumerate([0, 2, 1, 1]):
        st, _ = ref.receive_send(st, jnp.int32(i), _grads(1, seed=j)[0])
    np.testing.assert_array_equal(np.asarray(flat["vscale"]),
                                  np.asarray(st["vscale"]))
    _assert_trees_equal(st["theta0"], fa.master_params(flat))
    # custom (non-Schedule) callables go through the per-step fallback
    fa2 = FlatAlgorithm(make_algorithm(
        "dana-zero", HP, lambda t: 0.05 / (1.0 + 0.1
                                           * jnp.asarray(t, jnp.float32))))
    flat2 = fa2.init(PARAMS0, 2)
    flat2, _ = fa2.receive_send(flat2, jnp.int32(0), _grads(1)[0])
    assert int(flat2["t"]) == 1


def test_sent_staleness_lane():
    """The per-worker scalar lane carries the staleness signal: after a
    batch, worker i's sent_step is the master step of its LAST message
    (duplicates keep the latest), and pull-only sends refresh it."""
    algo = make_algorithm("dc-asgd", HP)
    fa = FlatAlgorithm(algo)
    flat = fa.init(PARAMS0, 4)
    assert np.all(np.asarray(fa.staleness(flat)) == 0.0)
    ids = jnp.asarray([1, 3, 1, 0], jnp.int32)
    g_flat = jnp.stack([fa.spec.pack(g) for g in _grads(4, seed=5)])
    flat, _, _ = fa.apply_batch(flat, ids, g_flat)
    lane = fa.lane.get(flat["wscal"], SENT_STEP)
    np.testing.assert_array_equal(np.asarray(lane), [4.0, 3.0, 0.0, 2.0])
    np.testing.assert_array_equal(np.asarray(fa.staleness(flat)),
                                  [0.0, 1.0, 4.0, 2.0])
    _, flat = fa.send_flat(flat, jnp.int32(2))      # rejoin-style pull
    assert float(fa.staleness(flat)[2]) == 0.0


def test_flat_rejects_non_family():
    with pytest.raises(ValueError, match="eligible"):
        FlatAlgorithm(make_algorithm("easgd", HP))


def test_rate_lane_trajectory_matches_tree():
    """The flat rate lane (interval EMA + last push time) advances
    bit-for-bit like DanaHetero.receive's (N,) vectors, message by
    message, duplicate ids included."""
    algo = make_algorithm("dana-hetero", HP)
    fa = FlatAlgorithm(algo)
    flat = fa.init(PARAMS0, 4)
    st = make_algorithm("dana-hetero", HP).init(PARAMS0, 4)
    ids = [2, 0, 2, 2, 1]
    nows = [0.3, 0.9, 1.0, 2.4, 2.5]
    for j, (i, now) in enumerate(zip(ids, nows)):
        g = _grads(1, seed=40 + j)[0]
        st = algo.receive(st, jnp.int32(i), g, jnp.float32(now))
        flat, _, _ = fa.apply_batch(
            flat, jnp.asarray([i], jnp.int32), fa.spec.pack(g)[None],
            jnp.asarray([now], jnp.float32))
    tree_f = fa.tree_state(flat)
    np.testing.assert_array_equal(np.asarray(tree_f["interval"]),
                                  np.asarray(st["interval"]))
    np.testing.assert_array_equal(np.asarray(tree_f["last_t"]),
                                  np.asarray(st["last_t"]))
    # and the resulting pull view matches the tree send (tolerance: the
    # weighted sum reduces over flat rows instead of leaf-by-leaf)
    vt, _ = algo.send(st, jnp.int32(2))
    vf, _ = fa.send(flat, jnp.int32(2))
    _assert_trees_close(vt, vf, 2e-6)


# ---------------------------------------------------------------------------
# the weighted-slab reduction send kernel
# ---------------------------------------------------------------------------
def test_send_kernel_matches_ref():
    """flat_send_view: Pallas (interpret) == the jitted jnp reference to
    1-ULP fma tolerance (two different XLA graphs contract fma
    differently; the BIT-EXACT contract lives on the production jnp
    path, flat == tree), incl. the adaptive (Nadam) denominator and the
    N-way rate-weighted mix."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    theta = jax.random.normal(ks[0], (48, 128))
    slab = jax.random.normal(ks[1], (5, 48, 128)) * 0.3
    u2 = jnp.abs(jax.random.normal(ks[2], (48, 128))) * 0.01
    w = jnp.abs(jax.random.normal(ks[3], (5,))) + 0.25
    c = jnp.float32(0.045)
    one = jnp.ones((1,))
    ref = jax.jit(flat_send_view_ref)
    ref_u2 = jax.jit(lambda *a: flat_send_view_ref(a[0], a[1], a[2],
                                                   a[3], u2=a[4]))
    a = flat_send_view(theta, slab[:1], one, c, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a),
                               np.asarray(ref(theta, slab[:1], one, c)),
                               rtol=2e-6, atol=2e-7)
    a = flat_send_view(theta, slab[:1], one, c, u2=u2, use_pallas=True)
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(ref_u2(theta, slab[:1], one, c, u2)),
        rtol=2e-6, atol=2e-7)
    a = flat_send_view(theta, slab, w, c, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a),
                               np.asarray(ref(theta, slab, w, c)),
                               rtol=2e-6, atol=2e-7)


@pytest.mark.parametrize("name", ["dana-zero", "lwp", "dana-nadam",
                                  "dana-hetero"])
def test_send_kernel_view_matches_tree_send(name):
    """Every look-ahead member's Pallas send (use_pallas=True, interpret
    off-TPU) reproduces its own tree send ON THE SAME STATE to 1-ULP
    fma tolerance (bit-exactness is the jnp path's contract, pinned by
    the fused-equivalence tests)."""
    algo = make_algorithm(name, HP)
    fa = FlatAlgorithm(algo, use_pallas=True)
    flat = fa.init(PARAMS0, 3)
    for j, i in enumerate([0, 2, 1, 2]):
        g = _grads(1, seed=60 + j)[0]
        flat = fa.receive(flat, jnp.int32(i), g, jnp.float32(j + 1.0))
    st = fa.tree_state(flat)            # the IDENTICAL state, unpacked
    vt, _ = jax.jit(algo.send)(st, jnp.int32(2))
    vf, _ = jax.jit(fa.send)(flat, jnp.int32(2))
    _assert_trees_close(vt, vf, 2e-6)


# ---------------------------------------------------------------------------
# gap-aware: the two-phase Pallas lowering vs the jnp oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 4])
def test_gap_pallas_matches_ref(k):
    """The (2, row_tiles) two-phase grid with SMEM-scratch norm partials
    reproduces the jnp reference (theta / v / sent / avg_step / hats /
    telemetry) to reduction-order tolerance — per-tile partial sums
    reorder the global norm — with duplicate ids chaining."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    R, N = 512, 3                 # 2 row tiles: the grid really sweeps
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
    outk = flat_master_update_batch_gap(
        theta, v, sent, avg, g, ids, lrs, gammas, cgs, vscales,
        gap_ema=0.99, n_elems=R * 128, telemetry=True, interpret=True)
    outr = jax.jit(lambda: flat_master_update_batch_ref(
        theta, v, None, None, sent, avg, g, ids, lrs, lrs, gammas, cgs,
        vscales, nesterov=False, gap_aware=True, gap_ema=0.99,
        n_elems=R * 128, hat_mode="theta", telemetry=True))()
    pairs = [(outk[0], outr[0]), (outk[1], outr[1]), (outk[2], outr[4]),
             (outk[4], outr[6]), (outk[5], outr[7])]
    for a, b in pairs:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-7)
    np.testing.assert_allclose(float(outk[3]), float(outr[5]), rtol=2e-6)


def test_gap_pallas_through_flat_algorithm():
    """End to end: a ga-asgd FlatAlgorithm forced onto the Pallas path
    (interpret off-TPU) tracks the default reference execution.  Uses a
    wide model so the state spans > 1 row tile — the two-phase grid
    really runs (asserted, so the test can never pass vacuously via the
    tiny-state ref fallback)."""
    from repro.kernels.flat_update.kernel import gap_pallas_supported
    init, grad_fn, _ = make_classifier_fns([8, 4096, 4])
    params0 = init(jax.random.PRNGKey(2))
    algo = make_algorithm("ga-asgd", HP)
    fa_p = FlatAlgorithm(algo, use_pallas=True)
    fa_r = FlatAlgorithm(make_algorithm("ga-asgd", HP), use_pallas=False)
    fp, fr = fa_p.init(params0, 3), fa_r.init(params0, 3)
    assert gap_pallas_supported(fa_p.spec.rows, 3)
    ids = jnp.asarray([1, 0, 1, 2], jnp.int32)
    grads = [jax.jit(grad_fn)(params0, TASK.batch(j % 3, 21 + j))
             for j in range(4)]
    g_flat = jnp.stack([fa_p.spec.pack(g) for g in grads])
    fp, hats_p, _ = fa_p.apply_batch(fp, ids, g_flat)
    fr, hats_r, _ = fa_r.apply_batch(fr, ids, g_flat)
    for key in ("theta", "v", "sent"):
        np.testing.assert_allclose(np.asarray(fp[key]),
                                   np.asarray(fr[key]),
                                   rtol=2e-6, atol=2e-7)
    np.testing.assert_allclose(float(fp["avg_step"]),
                               float(fr["avg_step"]), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(hats_p), np.asarray(hats_r),
                               rtol=2e-6, atol=2e-7)


# ---------------------------------------------------------------------------
# the unstacked wire: the fused receive stacks the drained gradients
# inside its own jit, in place at k = 1
# ---------------------------------------------------------------------------
def _stacked_receive(fa, k, telemetry):
    """The receive on a pre-stacked (k, R, 128) wire: ``apply_batch``
    on the one buffer the serve loop stacked before the dispatch."""
    inv_sqrt_p = 1.0 / float(np.sqrt(fa.spec.n_elems))

    def receive(flat, ids, nows, g, views):
        flat, hats, pres = fa.apply_batch(flat, ids, g, nows,
                                          telemetry=telemetry)
        hats = tuple(hats[j] for j in range(k))
        if not telemetry:
            return flat, hats, None, None
        d = pres - views
        return (flat, hats, jnp.sqrt(jnp.sum(d * d, axis=(1, 2)))
                * inv_sqrt_p, jnp.sqrt(jnp.sum(g * g, axis=(1, 2))))

    return jax.jit(receive)


@pytest.mark.parametrize("telemetry", [False, True])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_unstacked_receive_equals_stacked_apply_batch(k, telemetry):
    from repro.cluster.master import fused_flat_program

    fa = FlatAlgorithm(make_algorithm("dana-zero", HP))
    flat_a, flat_b = fa.init(PARAMS0, 4), fa.init(PARAMS0, 4)
    ids = jnp.asarray([1, 3, 1, 0][:k], jnp.int32)
    nows = jnp.zeros((k,), jnp.float32)
    grads = tuple(fa.spec.pack(g) for g in _grads(k, seed=17))
    views = (tuple(fa.spec.pack(jax.tree.map(lambda l: l + 0.01 * j,
                                             PARAMS0))
                   for j in range(k)) if telemetry else None)
    got = fused_flat_program(fa, k, telemetry)(
        flat_a, ids, nows, grads, views)[:4]
    want = _stacked_receive(fa, k, telemetry)(
        flat_b, ids, nows, jnp.stack(grads),
        jnp.stack(views) if telemetry else None)
    _assert_trees_equal(got, want)


def test_one_gradient_reaches_the_update_without_a_copy():
    """At k = 1 the gradient parameter of the lowered receive is only
    reshaped or broadcast to (1, R, 128), a bitcast of the same bytes:
    the program holds no concatenation of the gradient."""
    import re

    from repro.cluster.master import fused_flat_program

    fa = FlatAlgorithm(make_algorithm("dana-zero", HP))
    flat = fa.init(PARAMS0, 2)
    rows = fa.spec.rows
    sds = jax.ShapeDtypeStruct
    text = fused_flat_program(fa, 1, False).lower(
        flat, sds((1,), jnp.int32), sds((1,), jnp.float32),
        (sds((rows, 128), jnp.float32),), None).as_text()
    sig = text[text.index("func.func public @main("):]
    sig = sig[:sig.index(") -> (")]
    # the one (R, 128) parameter that is not donated state
    grad = [a for a, attrs in re.findall(
        rf"(%arg\d+): tensor<{rows}x128xf32>( \{{[^}}]*\}})?", sig)
        if "tf.aliasing_output" not in attrs]
    assert len(grad) == 1, sig
    uses = [ln.strip() for ln in text.splitlines()[1:]
            if re.search(rf"{grad[0]}\b", ln)
            and not ln.lstrip().startswith("func.func")]
    assert uses
    for ln in uses:
        assert re.search(rf"= stablehlo\.(broadcast_in_dim|reshape) "
                         rf"{grad[0]}\b", ln), ln
        assert f"-> tensor<1x{rows}x128xf32>" in ln, ln
    assert "stablehlo.concatenate" not in text


# ---------------------------------------------------------------------------
# buffer donation: the fused pass updates state in place
# ---------------------------------------------------------------------------
def test_flat_fused_donates_and_aliases_buffers():
    """The master's fused flat pass donates its state and the kernel
    aliases state inputs to outputs (input_output_aliases): the update
    lands in the SAME buffer — no copy of theta or the momentum slab —
    and the donated input is dead afterwards."""
    _, _, m = _masters("dana-zero", 4, use_kernel=True)
    spec = m._flat_algo.spec
    fn = m._get_fused_flat(4, False)
    st = m._flat_state
    ptr_theta = st["theta"].unsafe_buffer_pointer()
    ptr_v = st["v"].unsafe_buffer_pointer()
    ids = jnp.asarray([0, 1, 2, 3], jnp.int32)
    nows = jnp.zeros((4,), jnp.float32)
    grads = tuple(spec.pack(g) for g in _grads(4, seed=31))
    out_state, _, _, _, _ = fn(st, ids, nows, grads, None)
    assert out_state["theta"].unsafe_buffer_pointer() == ptr_theta
    assert out_state["v"].unsafe_buffer_pointer() == ptr_v
    assert st["theta"].is_deleted()
    m._flat_state = out_state           # keep the master coherent


def test_pull_views_survive_donation():
    """Pull views escape to worker threads; they must NOT alias the
    donated master state (a theta-sender's view is a copy)."""
    _, _, m = _masters("dc-asgd", 3, use_kernel=True)
    view, _ = m.initial_view(0)
    before = np.asarray(view).copy()
    fn = m._get_fused_flat(1, False)
    spec = m._flat_algo.spec
    m._flat_state, _, _, _, _ = fn(
        m._flat_state, jnp.asarray([0], jnp.int32),
        jnp.zeros((1,), jnp.float32),
        (spec.pack(_grads(1, seed=5)[0]),), None)
    np.testing.assert_array_equal(np.asarray(view), before)


# ---------------------------------------------------------------------------
# engine: flat execution reproduces the tree engine bit-for-bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,schedule", [
    ("dana-zero", None), ("nag-asgd", None), ("dana-nadam", None),
    ("dc-asgd", None), ("dana-dc", None), ("ga-asgd", None),
    # the closed matrix: asgd / lwp / dana-hetero / nadam-asgd run the
    # engine's flat execution too (hetero's rate lane rides the event
    # clock's ``now``)
    ("asgd", None), ("lwp", None), ("dana-hetero", None),
    ("nadam-asgd", None),
    # the lifted constant-lr restriction, end to end through the engine
    ("dana-zero", SCHED), ("dana-dc", SCHED), ("lwp", SCHED),
])
def test_engine_flat_execution_matches_tree(name, schedule):
    def run(use_kernel):
        algo = make_algorithm(name, HP, schedule)
        cfg = SimulationConfig(num_workers=3, total_grads=60, eval_every=20,
                               use_kernel=use_kernel)
        return run_simulation(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)

    h_t, h_f = run(False), run(True)
    with jax.disable_jit():
        h_r = run(False)                 # the op-by-op reference
    # ga-asgd's penalty reduction order drifts over the 60-step run, and
    # dana-hetero's weighted views feed the next gradients (same drift);
    # the Nadam pair's sqrt/divide fuse differently across lowerings
    tol = {"dana-nadam": 2e-6, "nadam-asgd": 2e-6, "ga-asgd": 5e-4,
           "dana-hetero": 5e-4}.get(name, 0.0)
    for h in (h_t, h_f):
        _assert_close_to_ref(h.final_params, h_r.final_params, ENGINE_ULPS,
                             tol)
        np.testing.assert_allclose(h.gap, h_r.gap, rtol=max(tol, 1e-5),
                                   atol=1e-7)
        assert h.time == h_r.time
        assert h.worker == h_r.worker
        assert h.lag == h_r.lag


def test_engine_flat_rejects_ineligible():
    algo = make_algorithm("easgd", HP)
    cfg = SimulationConfig(num_workers=2, total_grads=10, use_kernel=True)
    with pytest.raises(ValueError, match="eligible"):
        run_simulation(algo, GRAD_FN, PARAMS0, TASK.batch, cfg)
    # ssgd takes its own (synchronous) branch; use_kernel must not be
    # silently ignored there either
    cfg = SimulationConfig(num_workers=2, total_grads=10, use_kernel=True)
    with pytest.raises(ValueError, match="ssgd"):
        run_simulation(make_algorithm("ssgd", HP), GRAD_FN, PARAMS0,
                       TASK.batch, cfg)
