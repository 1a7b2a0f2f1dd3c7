"""Flat-state dispatch for the asynchronous algorithm family.

``FlatAlgorithm`` wraps a kernel-eligible ``Algorithm`` and executes its
receive->send hot path on flat (R, 128) buffers (``repro.core.flat``):
state is packed ONCE at init, every coalesced batch runs as ONE batched
kernel (Pallas on TPU, the jnp reference elsewhere — bit-identical for
the elementwise family), and pytrees only appear at the edges (incoming
gradients, outgoing views).

Kernel-eligible algorithms (exact types; subclasses that change the
update must take the generic tree path):

  asgd         no momentum: the family update with gamma = 0           [Alg. 1+2]
  dana-zero    per-worker momentum + v0 running sum + look-ahead   [Alg. 4]
  multi-asgd   per-worker momentum, heavy-ball (or Bengio) master  [Alg. 9]
  dana-slim    per-worker momentum, Bengio-NAG master              [Alg. 6]
  nag-asgd     shared momentum == the same kernel with N=1         [Alg. 8]
  lwp          shared momentum + tau-step look-ahead (hat "self")  [Alg. 3]
  dana-nadam   per-worker first moment + m0 sum + shared second
               moment, Nadam-preconditioned look-ahead             [Sec. 7]
  nadam-asgd   ONE shared (m, u) pair: the N=1 adaptive member     [Sec. 7]
  dc-asgd      + per-worker ``sent`` snapshot slab, delay
               compensation lam*g^2*(theta - sent_i)               [Alg. 10]
  dana-dc      DANA-Zero + delay compensation, snapshot = the
               look-ahead view the worker actually received        [Alg. 7]
  dana-hetero  rate-weighted look-ahead: the send mixes ALL N
               momentum slabs with w_j = r_j / r_i from the
               per-worker rate ScalarLane (weighted-slab kernel)   [Sec. 3]
  ga-asgd      + gap penalty 1 + G(theta - sent_i)/avg_step —
               the one non-elementwise member (global delta norm);
               two-phase Pallas grid on TPU, jnp ref (the
               cross-backend oracle) elsewhere                     [App. C]

Sends are declarative: each ``Algorithm`` *describes* its view
construction (``send_source`` / ``send_weights`` / ... class fields) and
``SendSpec`` is that description bound to the flat layout — the batched
kernel builds per-message look-ahead views from it (hat modes), and
pull-path sends run the standalone weighted-slab reduction kernel
(``send.py``) instead of ad-hoc tree axpy.

Learning-rate schedules are fully supported: the batched pass feeds
per-message lr(t+j) / lr(t+j+1) scalars plus the running lazy
momentum-correction ``vscale`` product into the kernel, so the fused
path reproduces the tree path's receive->send (Goyal correction
included) bit-for-bit for the elementwise family — there is no
constant-lr restriction.  Gap-aware and the hetero rate-weighted views
agree to reduction-order tolerance (norms/weighted sums reduce over the
flat buffer instead of leaf-by-leaf).

``eligibility_matrix()`` is the documented contract: which algorithms
are flat-eligible, send-kernel users, shard-eligible, shard-bit-exact,
and schedule-eligible.  CI asserts it (tests + the bench smoke) so a
silent eligibility regression fails loudly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ...core.flat import (FlatSpec, RATE_INTERVAL, RATE_LANE, RATE_LAST_T,
                          ScalarLane)
from ...core.schedules import Schedule
from .kernel import (_pick_block_rows, flat_master_update_batch_2d,
                     flat_master_update_batch_gap,
                     flat_master_update_batch_prefetch,
                     gap_pallas_supported)
from .ref import flat_master_update_batch_ref
from .send import flat_send_view


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# staleness signal slot: the master step worker i's ``sent`` snapshot was
# taken at (so t - lane[i] is the snapshot's age in master updates)
SENT_STEP = "sent_step"
_SENT_LANE = ScalarLane((SENT_STEP,))


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    """Static shape of one family member's receive rule."""
    momentum_key: str | None     # per-worker momentum state key; None
    #                              (asgd) packs a zero N=1 slab, gamma=0
    sum_key: str | None          # running-sum key (v0/m0) or None
    u2_key: str | None           # second-moment key (adaptive) or None
    nesterov: bool               # master update uses gamma*v' + cg*g
    shared_momentum: bool        # momentum not stacked (nag-asgd): N=1 slab
    grad_coef: float = 1.0       # cg: 1, or (1 - beta1) for Nadam
    gamma: float | None = None   # momentum coefficient override (asgd: 0)
    b2: float = 0.999
    eps: float = 1e-8
    sent_key: str | None = None  # per-worker sent-snapshot slab, or None
    sent_view: bool = False      # snapshot <- view (dana-dc) vs theta
    dc_lambda: float | None = None   # delay-compensation coefficient
    gap_aware: bool = False      # GA penalty: global norm over delta
    gap_ema: float = 0.99        # avg_step EMA coefficient
    rate_weighted: bool = False  # dana-hetero: rate lane + weighted hats
    rate_ema: float = 0.8        # interval EMA coefficient
    uses_vscale: bool = True     # lazy Goyal rescale (False: Nadam pair)
    staleness_lr: bool = False   # sa-asgd: lr / tau per message (scalar
    #                              lane only, no snapshot slab; the PR 4
    #                              per-message lrs carry the division so
    #                              the kernel is untouched)

    @property
    def elementwise(self) -> bool:
        """True iff every term is per-row local — the property row
        sharding and the batched Pallas lowering rest on.  The hetero
        weighted hat IS per-row (the N-way mix happens within a row)."""
        return not self.gap_aware

    @property
    def stateful_send(self) -> bool:
        """True iff a send WRITES master state (the sent-snapshot slab
        and/or the staleness lane stamp), so pure-view fast paths — warm
        hot-range closures, hot-row pulls — must fall back to
        ``send_flat`` and callers must keep the returned state."""
        return self.sent_key is not None or self.staleness_lr


@dataclasses.dataclass(frozen=True)
class SendSpec:
    """Static shape of one family member's send (view construction),
    bound to the flat layout:

        view_i = theta - c * sum_j w_j * slab[j]   [/ (sqrt(u2)+eps)]

    ``source`` names the flat buffer reduced into the view ("v0" — the
    running sum; "v" — the momentum slab; None — the view IS theta);
    the c factors mirror ``Algorithm._send_scale`` in the same order."""
    source: str | None           # "v0" | "v" | None
    stacked: bool = False        # reduce over ALL N slab rows
    weights: str = "ones"        # "ones" | "rate" (w_j = r_j / r_i)
    gamma: bool = False          # c *= gamma
    tau: bool = False            # c *= tau (lwp)
    vscale: bool = False         # c *= vscale
    adaptive: bool = False       # / (sqrt(u2) + eps)

    @property
    def hat_mode(self) -> str:
        """How the batched kernel builds per-message reply views.
        Keys off ``stacked`` exactly like the tree path's branch (a
        stacked source reduces over ALL N slab rows — ones weights sum
        them, rate weights mix them; an unstacked momentum source is
        the single shared row, hat "self")."""
        if self.source is None:
            return "theta"
        if self.source == "v0":
            return "v0"
        return "weighted" if self.stacked else "self"


def family_spec_for(algo) -> FamilySpec | None:
    """FamilySpec for ``algo``, or None if it must take the tree path."""
    from ...core.algorithms import (ASGD, DanaDC, DanaHetero, DanaNadam,
                                    DanaSlim, DanaZero, DCASGD, GapAware,
                                    LWP, MultiASGD, NadamASGD, NagASGD,
                                    SAASGD)
    t = type(algo)
    if t is ASGD:
        return FamilySpec(None, None, None, nesterov=False,
                          shared_momentum=True, gamma=0.0)
    if t is SAASGD:
        return FamilySpec(None, None, None, nesterov=False,
                          shared_momentum=True, gamma=0.0,
                          staleness_lr=True)
    if t is DanaZero:
        return FamilySpec("v", "v0", None, nesterov=False,
                          shared_momentum=False)
    if t is DanaHetero:
        return FamilySpec("v", "v0", None, nesterov=False,
                          shared_momentum=False, rate_weighted=True,
                          rate_ema=algo.RATE_EMA)
    if t is MultiASGD:
        return FamilySpec("v", None, None, nesterov=algo.nesterov,
                          shared_momentum=False)
    if t is DanaSlim:
        return FamilySpec("v", None, None, nesterov=True,
                          shared_momentum=False)
    if t is NagASGD:
        return FamilySpec("v", None, None, nesterov=algo.nesterov,
                          shared_momentum=True)
    if t is LWP:
        return FamilySpec("v", None, None, nesterov=False,
                          shared_momentum=True)
    if t is DanaNadam:
        return FamilySpec("m", "m0", "u", nesterov=True,
                          shared_momentum=False,
                          grad_coef=1.0 - algo.hp.momentum,
                          b2=algo.B2, eps=algo.EPS, uses_vscale=False)
    if t is NadamASGD:
        return FamilySpec("m", None, "u", nesterov=True,
                          shared_momentum=True,
                          grad_coef=1.0 - algo.hp.momentum,
                          b2=algo.B2, eps=algo.EPS, uses_vscale=False)
    if t is DCASGD:
        return FamilySpec("v", None, None, nesterov=False,
                          shared_momentum=False, sent_key="sent",
                          dc_lambda=algo.hp.dc_lambda)
    if t is DanaDC:
        return FamilySpec("v", "v0", None, nesterov=False,
                          shared_momentum=False, sent_key="sent",
                          sent_view=True, dc_lambda=algo.hp.dc_lambda)
    if t is GapAware:
        return FamilySpec("v", None, None, nesterov=False,
                          shared_momentum=False, sent_key="sent",
                          gap_aware=True, gap_ema=algo.EMA)
    return None


def send_spec_for(algo, fam: FamilySpec | None = None) -> SendSpec | None:
    """The algorithm's declarative send fields bound to the flat layout
    (its ``send_source`` state key mapped to the flat buffer name)."""
    fam = fam if fam is not None else family_spec_for(algo)
    if fam is None:
        return None
    if algo.send_source is None:
        return SendSpec(None)
    source = "v0" if algo.send_source == fam.sum_key else "v"
    return SendSpec(source, stacked=algo.send_stacked,
                    weights=algo.send_weights, gamma=algo.send_gamma,
                    tau=algo.send_tau, vscale=algo.send_vscale,
                    adaptive=algo.send_adaptive)


def kernel_eligible(algo) -> bool:
    """True iff ``algo``'s hot path can run on the flat fused kernel."""
    return family_spec_for(algo) is not None


def shard_bitexact(algo) -> bool:
    """True iff the row-sharded master reproduces the single flat master
    bit-for-bit for ``algo`` (elementwise update rules only: the
    gap-aware penalty sums per-shard norm partials, which reorders the
    reduction)."""
    fam = family_spec_for(algo)
    return fam is not None and fam.elementwise


# the documented flat-eligibility set; CI (tests + the bench smoke)
# asserts eligibility_matrix() against it so regressions fail loudly
FLAT_ELIGIBLE = ("asgd", "dana-dc", "dana-hetero", "dana-nadam",
                 "dana-slim", "dana-zero", "dc-asgd", "ga-asgd", "lwp",
                 "multi-asgd", "nadam-asgd", "nag-asgd", "sa-asgd")
# the subset whose SEND constructs a look-ahead view through the
# weighted-slab reduction kernel (everyone else sends theta itself)
SEND_KERNEL = ("dana-dc", "dana-hetero", "dana-nadam", "dana-zero",
               "lwp")


def eligibility_matrix() -> dict[str, dict[str, bool]]:
    """{algorithm name: {flat, send_kernel, schedule, shard,
    shard_bitexact}} for the whole registry.

    * ``flat`` — hot path runs on the flat fused kernel;
    * ``send_kernel`` — the send is a look-ahead built by the
      weighted-slab reduction kernel (vs sending theta itself);
    * ``schedule`` — flat execution supports moving lr schedules
      (per-message lr(t)/lr(t+1) + the lazy vscale rescale in-kernel);
    * ``shard`` — the row-sharded multi-master supports it (gap-aware
      rides a per-message cross-shard norm exchange);
    * ``shard_bitexact`` — sharded == single master bit-for-bit.
    """
    from ...core.algorithms import REGISTRY, make_algorithm
    out = {}
    for name in sorted(REGISTRY):
        algo = make_algorithm(name)
        fam = family_spec_for(algo)
        send = send_spec_for(algo, fam)
        out[name] = {
            "flat": fam is not None,
            "send_kernel": send is not None and send.source is not None,
            "schedule": fam is not None,
            "shard": fam is not None,
            "shard_bitexact": fam is not None and fam.elementwise,
        }
    return out


# ---------------------------------------------------------------------------
# state <-> flat buffers
# ---------------------------------------------------------------------------
def pack_state(algo, state: dict, spec: FlatSpec | None = None):
    """Algorithm state dict -> flat dict {theta, v, [v0], [u2], [sent],
    [wscal], [rate], [tau], [avg_step], t, ...}."""
    fam = family_spec_for(algo)
    if spec is None:
        spec = FlatSpec.from_tree(state["theta0"])
    flat = {"theta": spec.pack(state["theta0"]),
            "t": state["t"], "lr_prev": state["lr_prev"]}
    if fam.momentum_key is None:
        # momentum-free (asgd): a zero N=1 slab keeps the kernel shape;
        # gamma = 0 makes every row update ignore it bit-exactly
        flat["v"] = jnp.zeros((1, spec.rows, flat["theta"].shape[-1]),
                              jnp.float32)
    elif fam.shared_momentum:
        flat["v"] = spec.pack(state[fam.momentum_key])[None]
    else:
        flat["v"] = spec.pack_stacked(state[fam.momentum_key])
    if fam.sum_key is not None:
        flat["v0"] = spec.pack(state[fam.sum_key])
    if fam.u2_key is not None:
        flat["u2"] = spec.pack(state[fam.u2_key])
    if fam.sent_key is not None:
        flat["sent"] = spec.pack_stacked(state[fam.sent_key])
        # staleness lane: every snapshot is as old as the adoption point
        flat["wscal"] = _SENT_LANE.init(
            flat["sent"].shape[0], **{SENT_STEP: state["t"]})
    elif fam.staleness_lr:
        # scalar-only staleness: sent_t rides the lane, no snapshot slab
        flat["wscal"] = _SENT_LANE.init(
            state["sent_t"].shape[0], **{SENT_STEP: state["sent_t"]})
    if fam.rate_weighted:
        flat["rate"] = RATE_LANE.pack({RATE_INTERVAL: state["interval"],
                                       RATE_LAST_T: state["last_t"]})
    if getattr(algo, "send_tau", False):
        flat["tau"] = state["tau"]
    if fam.gap_aware:
        flat["avg_step"] = state["avg_step"]
    if "vscale" in state:
        flat["vscale"] = state["vscale"]
    return flat, spec


_ROW_KEYS = ("theta", "v", "v0", "u2", "sent")   # buffers laid out by row


def slice_flat(flat: dict, r0: int, r1: int) -> dict:
    """Row-range shard of a flat state dict.

    Every buffer keyed in ``_ROW_KEYS`` is sliced to rows [r0, r1) of its
    (next-to-last) row axis — the (N, R, 128) momentum/sent slabs keep
    their worker axis — while scalars (t, lr_prev, vscale, tau,
    avg_step) and the per-worker scalar lanes (wscal, rate) are COPIED
    (not aliased: each shard's fused pass donates its state, so shards
    must never share a buffer).  Because every elementwise family update
    rule is per row (the hetero weighted sum mixes slab rows within one
    row), running the SAME ``FlatAlgorithm.apply_batch`` on the slice
    advances exactly the rows a shard owns, bit-identically to the
    full-state call (tested)."""
    return {k: (v[..., r0:r1, :] if k in _ROW_KEYS else jnp.copy(v))
            for k, v in flat.items()}


def merge_flat(pieces: list[dict]) -> dict:
    """Reassemble range-ordered shard states into one full flat state.

    Row buffers concatenate along the row axis; scalars and the scalar
    lanes are taken from the first shard (every shard applies every
    message with the same timestamps, so their t / lr_prev / vscale /
    wscal / rate trajectories are identical; avg_step too — sharded
    gap-aware feeds every shard the same combined norm)."""
    out = dict(pieces[0])
    for k in _ROW_KEYS:
        if k in out:
            out[k] = jnp.concatenate([p[k] for p in pieces], axis=-2)
    return out


def unpack_state(algo, flat: dict, spec: FlatSpec) -> dict:
    """Flat dict -> the algorithm's pytree state dict."""
    fam = family_spec_for(algo)
    state = {"theta0": spec.unpack(flat["theta"]),
             "t": flat["t"], "lr_prev": flat["lr_prev"]}
    if fam.momentum_key is None:
        pass                                   # asgd: no momentum state
    elif fam.shared_momentum:
        state[fam.momentum_key] = spec.unpack(flat["v"][0])
    else:
        state[fam.momentum_key] = spec.unpack_stacked(flat["v"])
    if fam.sum_key is not None:
        state[fam.sum_key] = spec.unpack(flat["v0"])
    if fam.u2_key is not None:
        state[fam.u2_key] = spec.unpack(flat["u2"])
    if fam.sent_key is not None:
        state[fam.sent_key] = spec.unpack_stacked(flat["sent"])
    if fam.staleness_lr:
        state["sent_t"] = _SENT_LANE.get(flat["wscal"], SENT_STEP)
    if fam.rate_weighted:
        state["interval"] = RATE_LANE.get(flat["rate"], RATE_INTERVAL)
        state["last_t"] = RATE_LANE.get(flat["rate"], RATE_LAST_T)
    if "tau" in flat:
        state["tau"] = flat["tau"]
    if fam.gap_aware:
        state["avg_step"] = flat["avg_step"]
    if "vscale" in flat:
        state["vscale"] = flat["vscale"]
    return state


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def prefetch_pays(rows: int, n: int, k: int, *, n_slabs: int = 1,
                  weighted: bool = False, gap: bool = False) -> bool:
    """Memory-tier routing rule: the scalar-prefetch kernel pays exactly
    when the dense full-slab grid's resident window (every worker row,
    per slab) forces SMALLER row tiles than the k-shaped prefetch window
    — or cannot tile at all.  While the dense slab still fits the same
    tile, its 2N streams are one sequential burst and the per-message
    window bookkeeping (scratch loads/flushes) would only add overhead;
    once N shrinks the dense tiles, the 2u-stream prefetch grid keeps
    the large tiles AND drops the untouched workers' traffic."""
    window_p = 3 if gap else k + 2 + (k if weighted else 0)
    try:
        pf_block = _pick_block_rows(rows, window_p, n_slabs)
    except ValueError:
        return False                      # nothing tiles; ref path serves
    try:
        dense_block = _pick_block_rows(rows, n, n_slabs)
    except ValueError:
        return True                       # only the prefetch grid tiles
    return dense_block < pf_block


def flat_master_update_batch(theta, v, v0, u2, sent, avg_step, g, ids,
                             lrs, lrs_next, gammas, cgs, vscales, *,
                             nesterov, b2=0.999, eps=1e-8, dc_lambda=None,
                             sent_view=False, gap_aware=False,
                             gap_ema=0.99, n_elems=0, hat_mode=None,
                             hcs=None, weights=None, telemetry=False,
                             use_pallas=None, prefetch=True):
    """Pallas on TPU, jnp reference elsewhere (bit-identical off-TPU).

    The Pallas elementwise path is a two-tier memory hierarchy:
    ``prefetch=True`` (the default) routes each batch with
    ``prefetch_pays`` — the scalar-prefetch kernel (slab traffic 2u
    streams for u unique senders, VMEM budget independent of N) exactly
    when the dense grid's N-row window shrinks its tiles or cannot tile
    at all, the dense full-slab kernel while the whole slab still rides
    one tile (its 2N streams are one sequential burst there).
    ``prefetch=False`` forces the PR-2 full-slab kernel (kept as the
    bench baseline).  Gap-aware lowers to the two-phase (2, row_tiles)
    grid chained per message when the state is big enough to tile (see
    ``kernel.gap_pallas_supported``), ordering the variants by the same
    routing rule; the jitted jnp reference is the cross-backend oracle
    and serves tiny states."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas and gap_aware:
        order = (False,)
        if prefetch:
            order = ((True, False)
                     if prefetch_pays(theta.shape[-2], v.shape[0],
                                      g.shape[0], n_slabs=2, gap=True)
                     else (False, True))
        for pf in order:
            if not gap_pallas_supported(theta.shape[-2], v.shape[0],
                                        prefetch=pf):
                continue
            theta, v, sent, avg_step, hats, pres = \
                flat_master_update_batch_gap(
                    theta, v, sent, avg_step, g, ids, lrs, gammas, cgs,
                    vscales, gap_ema=gap_ema, n_elems=n_elems,
                    telemetry=telemetry, interpret=not _on_tpu(),
                    prefetch=pf)
            return theta, v, None, None, sent, avg_step, hats, pres
    if use_pallas and not gap_aware:
        if prefetch:
            prefetch = prefetch_pays(
                theta.shape[-2], v.shape[0], g.shape[0],
                n_slabs=2 if sent is not None else 1,
                weighted=hat_mode == "weighted")
        fn = (flat_master_update_batch_prefetch if prefetch
              else flat_master_update_batch_2d)
        theta, v, v0, u2, sent, hats, pres = fn(
            theta, v, v0, u2, sent, g, ids, lrs, lrs_next, gammas, cgs,
            vscales, nesterov=nesterov, b2=b2, eps=eps,
            dc_lambda=dc_lambda, sent_view=sent_view, hat_mode=hat_mode,
            hcs=hcs, weights=weights, telemetry=telemetry,
            interpret=not _on_tpu())
        return theta, v, v0, u2, sent, avg_step, hats, pres
    return flat_master_update_batch_ref(
        theta, v, v0, u2, sent, avg_step, g, ids, lrs, lrs_next, gammas,
        cgs, vscales, nesterov=nesterov, b2=b2, eps=eps,
        dc_lambda=dc_lambda, sent_view=sent_view, gap_aware=gap_aware,
        gap_ema=gap_ema, n_elems=n_elems, hat_mode=hat_mode, hcs=hcs,
        weights=weights, telemetry=telemetry)


# ---------------------------------------------------------------------------
# the flat executor
# ---------------------------------------------------------------------------
class FlatAlgorithm:
    """Flat-state executor with the Algorithm calling convention.

    ``init``/``send``/``receive_send``/``master_params`` mirror
    ``repro.core.algorithms.Algorithm`` but the state is the flat dict, so
    the engine and the cluster master can swap it in without changing
    their loops.  Use ``tree_state`` to get the pytree state back.

    ``send``/``send_flat`` return the (possibly) UPDATED flat state: the
    sent-snapshot family refreshes worker i's slab row and its staleness
    lane slot on every send — callers must keep the returned state (the
    pure-view fast path is ``_view_flat``)."""

    def __init__(self, algo, use_pallas: bool | None = None):
        fam = family_spec_for(algo)
        if fam is None:
            raise ValueError(
                f"{algo.name!r} is not kernel-eligible; flat execution "
                f"covers exactly the asynchronous update family")
        self.algo = algo
        self.fam = fam
        self.send_spec = send_spec_for(algo, fam)
        self.name = algo.name
        self.hp = algo.hp
        self.schedule = algo.schedule
        self.use_pallas = use_pallas
        self.lane = (_SENT_LANE if fam.stateful_send else None)
        self.spec: FlatSpec | None = None

    # -- Algorithm API ---------------------------------------------------
    def init(self, params, num_workers: int) -> dict:
        state = self.algo.init(params, num_workers)
        return self.adopt(state)

    def adopt(self, state: dict) -> dict:
        """Pack an ALREADY-initialized algorithm state into flat form."""
        flat, self.spec = pack_state(self.algo, state)
        return flat

    def master_params(self, flat: dict):
        return self.spec.unpack(flat["theta"])

    def tree_state(self, flat: dict) -> dict:
        return unpack_state(self.algo, flat, self.spec)

    def staleness(self, flat: dict):
        """Per-worker age (in master updates) of the ``sent`` snapshots,
        from the scalar lane — or None for snapshot-free members."""
        if self.lane is None:
            return None
        return (jnp.asarray(flat["t"], jnp.float32)
                - self.lane.get(flat["wscal"], SENT_STEP))

    def batch_staleness(self, flat: dict, wids, k: int):
        """Per-message sent-snapshot staleness for a k-message batch,
        BEFORE ``apply_batch`` consumes (donates) ``flat``: message j
        applies at master step ``t + j`` against worker ``wids[j]``'s
        snapshot, and a duplicate id inside the batch chains through its
        own in-batch re-stamp (exactly the stamps ``apply_batch`` would
        have written after j+1 messages).  Returns a (k,) f32 vector, or
        None for snapshot-free members."""
        if self.lane is None:
            return None
        sent = self.lane.get(flat["wscal"], SENT_STEP)
        t = jnp.asarray(flat["t"], jnp.float32)
        out = []
        for j in range(k):                       # k static, <= coalesce
            out.append(t + j - sent[wids[j]])
            sent = sent.at[wids[j]].set(t + (j + 1))
        return jnp.stack(out)

    # -- the flat send path ----------------------------------------------
    def _gamma(self) -> float:
        return (self.fam.gamma if self.fam.gamma is not None
                else self.hp.momentum)

    def _rate_weights(self, flat: dict, i):
        """w_j = r_j / r_i from the rate lane (mirror
        ``Algorithm._send_rate_weights`` bit-for-bit)."""
        interval = RATE_LANE.get(flat["rate"], RATE_INTERVAL)
        rates = 1.0 / jnp.maximum(interval, 1e-6)
        return rates / jnp.maximum(rates[i], 1e-6)

    def _send_scale(self, flat: dict):
        """c(t) through the SHARED ``compose_send_scale`` (one factor
        order for tree and flat sends)."""
        from ...core.algorithms import compose_send_scale
        sp = self.send_spec
        return compose_send_scale(
            self._sched(flat["t"]),
            gamma=jnp.float32(self.hp.momentum) if sp.gamma else None,
            tau=flat["tau"] if sp.tau else None,
            vscale=(flat.get("vscale", jnp.float32(1.0)) if sp.vscale
                    else None))

    def _view_flat(self, flat: dict, i=0):
        """The view the family's send computes, on flat rows — the
        weighted-slab reduction kernel (send.py) for every look-ahead
        member, theta itself for the rest."""
        sp = self.send_spec
        if sp.source is None:
            # a COPY, not theta itself: pull views escape to workers
            # while the donated fused pass overwrites theta in place
            return jnp.copy(flat["theta"])
        slab = flat["v0"][None] if sp.source == "v0" else flat["v"]
        if sp.weights == "rate":
            w = self._rate_weights(flat, jnp.asarray(i, jnp.int32))
        else:
            w = jnp.ones((slab.shape[0],), jnp.float32)
        return flat_send_view(flat["theta"], slab, w,
                              self._send_scale(flat),
                              u2=flat.get("u2") if sp.adaptive else None,
                              eps=self.fam.eps, use_pallas=self.use_pallas)

    def view_rows(self, flat: dict, i, r0: int, r1: int):
        """Hot-row pull: the send view over ONLY rows [r0, r1).

        Every look-ahead reduction is elementwise per row, so slicing the
        operands commutes with the reduction bit-for-bit — this equals
        ``_view_flat(flat, i)[r0:r1]`` (the same row-locality the sharded
        master's per-range sends rely on).  Pure (no state update), so it
        is only a valid SEND for the snapshot-free members
        (``fam.sent_key is None``); sent-snapshot callers must fall back
        to the full-range ``send_flat``.  ``r0``/``r1`` are static:
        callers jit one closure per distinct hot range."""
        if r1 <= r0:
            # empty intersection (sharded hot pull outside this shard's
            # range): a zero-row view, no kernel launch
            return jnp.zeros((0, flat["theta"].shape[-1]), jnp.float32)
        sp = self.send_spec
        th = flat["theta"][r0:r1]
        if sp.source is None:
            return jnp.copy(th)
        slab = flat["v0"][None] if sp.source == "v0" else flat["v"]
        if sp.weights == "rate":
            w = self._rate_weights(flat, jnp.asarray(i, jnp.int32))
        else:
            w = jnp.ones((slab.shape[0],), jnp.float32)
        u2 = flat.get("u2") if sp.adaptive else None
        return flat_send_view(th, slab[:, r0:r1], w,
                              self._send_scale(flat),
                              u2=None if u2 is None else u2[r0:r1],
                              eps=self.fam.eps, use_pallas=self.use_pallas)

    def send_flat(self, flat: dict, i=0):
        """(view rows, updated flat): the wire-format send.  For the
        stateful-send family this stamps the staleness lane with t and —
        when a snapshot slab exists — writes worker i's slab row (the
        look-ahead view for dana-dc, theta otherwise — mirroring each
        algorithm's send); sa-asgd carries the lane stamp alone."""
        i = jnp.asarray(i, jnp.int32)
        view = self._view_flat(flat, i)
        if self.lane is None:
            return view, flat
        new = dict(flat)
        if self.fam.sent_key is not None:
            sval = view if self.fam.sent_view else flat["theta"]
            new["sent"] = jax.lax.dynamic_update_index_in_dim(
                flat["sent"], sval, i, axis=0)
        new["wscal"] = self.lane.set_at(flat["wscal"], SENT_STEP, i,
                                        flat["t"])
        return view, new

    def send(self, flat: dict, i=0):
        view, flat = self.send_flat(flat, i)
        return self.spec.unpack(view), flat

    # -- per-message schedule scalars -------------------------------------
    def _sched(self, t):
        return jnp.asarray(self.schedule(t), jnp.float32)

    def _sched_vec(self, t0, k: int, off: int):
        """lr(t0 + off + j) for j in [0, k) — vectorized for the standard
        ``Schedule`` (elementwise, so bit-equal to scalar calls), one
        call per step for custom callables."""
        if isinstance(self.schedule, Schedule):
            steps = t0 + jnp.arange(off, k + off, dtype=jnp.int32)
            return jnp.broadcast_to(self._sched(steps), (k,))
        return jnp.stack([self._sched(t0 + (j + off)) for j in range(k)])

    def _msg_scalars(self, flat: dict, k: int):
        """Per-message (lrs, lrs_next, gammas, cgs, vscales, hcs): the
        update rate lr(t+j), the look-ahead rate lr(t+j+1), the running
        momentum-correction product, and the hat coefficient (the send
        scale at the post-update step, composed in _send_scale's factor
        order) — the exact sequence the tree path's k sequential
        receive->send rounds would produce."""
        lrs = self._sched_vec(flat["t"], k, 0)
        lrs_next = self._sched_vec(flat["t"], k, 1)
        gammas = jnp.full((k,), self._gamma(), jnp.float32)
        cgs = jnp.full((k,), self.fam.grad_coef, jnp.float32)
        if self.fam.uses_vscale and "vscale" in flat:
            # mirror Algorithm._lr_and_vscale message by message
            vs, prev, seq = flat["vscale"], flat["lr_prev"], []
            for j in range(k):
                corr = jnp.where(prev > 0,
                                 lrs[j] / jnp.maximum(prev, 1e-20), 1.0)
                vs = vs * jnp.maximum(corr, 1e-30)
                seq.append(vs)
                prev = lrs[j]
            vscales = jnp.stack(seq)
        else:
            vscales = jnp.ones((k,), jnp.float32)
        from ...core.algorithms import compose_send_scale
        sp = self.send_spec
        hcs = compose_send_scale(
            lrs_next,
            gamma=jnp.float32(self.hp.momentum) if sp.gamma else None,
            tau=flat["tau"] if sp.tau else None,
            vscale=vscales if sp.vscale else None)
        return lrs, lrs_next, gammas, cgs, vscales, hcs

    def _rate_trajectory(self, flat: dict, wids, nows, k: int):
        """Advance the rate lane through the k messages and collect the
        per-message weight rows w_jm = r_m / r_{i_j} — mirroring
        DanaHetero.receive's interval EMA + DanaHetero.send's weights
        message by message (dup ids chain through their own updates)."""
        ema = self.fam.rate_ema
        interval = RATE_LANE.get(flat["rate"], RATE_INTERVAL)
        last_t = RATE_LANE.get(flat["rate"], RATE_LAST_T)
        rows = []
        for j in range(k):
            i = wids[j]
            now = jnp.asarray(nows[j], jnp.float32)
            dt = jnp.maximum(now - last_t[i], 1e-6)
            interval = interval.at[i].set(
                ema * interval[i] + (1 - ema) * dt)
            last_t = last_t.at[i].set(now)
            rates = 1.0 / jnp.maximum(interval, 1e-6)
            rows.append(rates / jnp.maximum(rates[i], 1e-6))
        lane = RATE_LANE.pack({RATE_INTERVAL: interval,
                               RATE_LAST_T: last_t})
        return jnp.stack(rows), lane

    def apply_batch(self, flat: dict, ids, g_flat, nows=None, *,
                    telemetry: bool = False):
        """Apply k packed messages in one fused pass.

        ids (k,) int32 worker ids; g_flat (k, R, 128) packed gradients;
        nows (k,) f32 message timestamps (the rate-weighted member's
        telemetry; zeros when absent).
        Returns (flat', hats (k,R,128), thetas_pre or None).

        ``g_flat`` is the kernel's operand, one contiguous (k, R, 128)
        buffer; where it is formed depends on the serve loop.  The
        threaded master's fused receive (``fused_flat_program``) gets
        the k drained (R, 128) gradients unstacked and stacks them
        inside its own jit: at k = 1 a bitcast, so the kernel reads the
        gradient in place.  The sharded servers stack eagerly before
        their receive, and the process backend stages its shared-memory
        ring slices into one host buffer and ships ONE device transfer
        per batch.
        """
        k = g_flat.shape[0]
        if (self.fam.gap_aware and self.spec is not None
                and flat["theta"].shape[-2] != self.spec.rows):
            raise ValueError(
                "gap-aware updates need the FULL row space (the penalty "
                "is a global norm); row-range shards must use the "
                "gap_partial/apply_gap_message exchange path")
        wids = ids                               # real ids (lane stamps)
        if self.fam.shared_momentum:
            ids = jnp.zeros_like(ids)            # one shared slab row
        if nows is None:
            nows = jnp.zeros((k,), jnp.float32)
        lrs, lrs_next, gammas, cgs, vscales, hcs = \
            self._msg_scalars(flat, k)
        if self.fam.staleness_lr:
            # Zhang et al.: lr_j / tau_j, tau floored at 1 (synchronous
            # pushes run at full rate).  Folding the division into the
            # per-message lrs keeps the kernel untouched and matches the
            # tree path's per-receive division bit-for-bit.
            lrs = lrs / jnp.maximum(self.batch_staleness(flat, wids, k),
                                    1.0)
        weights = rate_lane = None
        if self.fam.rate_weighted:
            weights, rate_lane = self._rate_trajectory(flat, wids, nows, k)
        elif self.send_spec.hat_mode == "weighted":
            # stacked source with "ones" weights: a plain slab sum
            weights = jnp.ones((k, flat["v"].shape[0]), jnp.float32)
        theta, v, v0, u2, sent, avg_step, hats, pres = \
            flat_master_update_batch(
                flat["theta"], flat["v"], flat.get("v0"), flat.get("u2"),
                flat.get("sent"), flat.get("avg_step"), g_flat, ids, lrs,
                lrs_next, gammas, cgs, vscales,
                nesterov=self.fam.nesterov, b2=self.fam.b2,
                eps=self.fam.eps, dc_lambda=self.fam.dc_lambda,
                sent_view=self.fam.sent_view,
                gap_aware=self.fam.gap_aware, gap_ema=self.fam.gap_ema,
                n_elems=self.spec.n_elems if self.spec is not None else 0,
                hat_mode=self.send_spec.hat_mode, hcs=hcs,
                weights=weights, telemetry=telemetry,
                use_pallas=self.use_pallas)
        new = dict(flat)
        new.update(theta=theta, v=v, t=flat["t"] + k, lr_prev=lrs[-1])
        if v0 is not None:
            new["v0"] = v0
        if u2 is not None:
            new["u2"] = u2
        if sent is not None:
            new["sent"] = sent
        if self.lane is not None:
            wscal = flat["wscal"]
            for j in range(k):                   # k static, <= coalesce
                wscal = self.lane.set_at(wscal, SENT_STEP, wids[j],
                                         flat["t"] + (j + 1))
            new["wscal"] = wscal
        if rate_lane is not None:
            new["rate"] = rate_lane
        if avg_step is not None:
            new["avg_step"] = avg_step
        if self.fam.uses_vscale and "vscale" in flat:
            new["vscale"] = vscales[-1]
        return new, hats, pres

    # -- sharded gap-aware hot path (cross-shard norm exchange) ----------
    # The gap penalty needs ||theta - sent_i|| over ALL rows; a row-range
    # shard only holds some.  The sharded master runs gap-aware members
    # one message at a time in three steps: gap_partial (this shard's
    # sum d^2) -> combine across shards -> apply_gap_message with the
    # global sum -> combine ||v'||^2 partials -> finish_gap_message
    # (avg_step EMA).  Formulas mirror the batched reference exactly,
    # with the in-jit reductions replaced by the exchanged totals.
    def gap_partial(self, flat: dict, i):
        """This row range's contribution to ||theta - sent_i||^2."""
        si = jax.lax.dynamic_index_in_dim(flat["sent"], i, axis=0,
                                          keepdims=False)
        d = flat["theta"] - si
        return jnp.sum(d * d)

    def apply_gap_message(self, flat: dict, i, g_row, gap2, view=None):
        """One gap-aware message on this shard's rows, with the
        cross-shard combined ``gap2 = sum_s sum d^2``.  Returns
        (flat_mid, hat, vn2_partial, lr, vscale, d2, g2) — ``flat_mid``
        still has the OLD avg_step (finish_gap_message completes it once
        the v-norm partials are combined); d2/g2 are this shard's
        telemetry partials (zeros when ``view`` is None)."""
        lrs, _, gammas, cgs, vscales, _ = self._msg_scalars(flat, 1)
        lr, gamma, cg, vs = lrs[0], gammas[0], cgs[0], vscales[0]
        sqrt_p = jnp.sqrt(jnp.asarray(self.spec.n_elems, jnp.float32))
        i = jnp.asarray(i, jnp.int32)
        pre = flat["theta"]
        vi = jax.lax.dynamic_index_in_dim(flat["v"], i, axis=0,
                                          keepdims=False)
        gap = jnp.sqrt(gap2) / sqrt_p
        penalty = 1.0 + gap / jnp.maximum(flat["avg_step"], 1e-12)
        gj = (1.0 / penalty) * g_row
        v_new = gamma * vi + cg * ((1.0 / vs) * gj)
        theta = ((-lr) * vs) * v_new + pre
        new = dict(flat)
        new.update(
            theta=theta,
            v=jax.lax.dynamic_update_index_in_dim(flat["v"], v_new, i,
                                                  axis=0),
            sent=jax.lax.dynamic_update_index_in_dim(flat["sent"], theta,
                                                     i, axis=0),
            wscal=self.lane.set_at(flat["wscal"], SENT_STEP, i,
                                   flat["t"] + 1),
            t=flat["t"] + 1, lr_prev=lrs[0], vscale=vs)
        vn2 = jnp.sum(v_new * v_new)
        if view is not None:
            dd = pre - view
            d2, g2 = jnp.sum(dd * dd), jnp.sum(g_row * g_row)
        else:
            d2 = g2 = jnp.zeros((), jnp.float32)
        return new, theta, vn2, lr, vs, d2, g2

    def finish_gap_message(self, flat: dict, vn2, lr, vs):
        """avg_step EMA from the cross-shard combined ||v'||^2."""
        sqrt_p = jnp.sqrt(jnp.asarray(self.spec.n_elems, jnp.float32))
        step_rms = lr * vs * jnp.sqrt(vn2) / sqrt_p
        new = dict(flat)
        new["avg_step"] = (self.fam.gap_ema * flat["avg_step"]
                           + (1 - self.fam.gap_ema) * step_rms)
        return new

    def receive_send(self, flat: dict, i, grad, now=0.0):
        """One message through the batched path (k=1)."""
        g_flat = self.spec.pack(grad)[None]
        ids = jnp.asarray(i, jnp.int32).reshape(1)
        nows = jnp.asarray(now, jnp.float32).reshape(1)
        flat, hats, _ = self.apply_batch(flat, ids, g_flat, nows)
        return flat, self.spec.unpack(hats[0])

    def receive(self, flat: dict, i, grad, now=0.0):
        flat, _ = self.receive_send(flat, i, grad, now)
        return flat
