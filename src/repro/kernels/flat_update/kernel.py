"""Pallas TPU kernels: k coalesced master messages in ONE pallas_call.

PR 1's fused receive amortized dispatch but still ran k sequential kernel
invocations (one per drained message), each re-reading theta/v0 from HBM.
The batched kernel applies the whole coalesced batch in one grid:

    grid = (row_tiles, k)          # messages innermost

For a fixed row tile the k messages run back-to-back while theta / v / v0
/ u2 / sent stay resident in VMEM — the HBM traffic for the master state
drops from O(k * state) to O(state) + O(k * grad) per batch, which is the
whole game for a bandwidth-bound master (paper App. C.1).  Output blocks
whose index map ignores the message axis (theta, v, v0, u2, sent) are
revisited across the inner loop, the standard Pallas accumulation
pattern (revisits are consecutive — a TPU pipelining requirement); the
incoming gradients g (k,R,128) and outgoing views hat (k,R,128) stream.
State inputs are aliased to their outputs (``input_output_aliases``), so
when the caller donates its buffers the update runs in place and the
state traffic halves again.

Per-worker slabs (momentum v and, for the delay-compensated family, the
``sent`` snapshot) live as (N, R, 128) stacks; the row for worker ids[j]
is selected with a dynamic slice inside the kernel, so duplicate worker
ids within a batch chain correctly (message j+1 sees j's update AND j's
refreshed snapshot).

Two lowerings cover the elementwise family:

* ``flat_master_update_batch_2d`` — the PR-2 full-slab kernel: every
  grid step streams ALL N slab rows through VMEM
  (``slab_spec`` below), so slab traffic is 2N streams per batch and
  ``_pick_block_rows`` must divide the tile budget by N.
* ``flat_master_update_batch_prefetch`` — the memory-tier kernel: the
  batch's worker ids ride in as a **scalar-prefetch** operand
  (``pltpu.PrefetchScalarGridSpec``) and the slab BlockSpec index maps
  select ONE worker row per grid step, so only the u <= k touched slabs
  are ever DMA'd (2u streams; untouched rows are preserved through
  ``input_output_aliases``).  Duplicate ids chain through a (k, block_r,
  128) VMEM scratch window: a slab row is fetched once at its FIRST
  occurrence (the fetch schedule forward-fills the block index so
  repeats don't re-read a row the window already owns), every message
  updates its window slot, and each touched row is flushed once at/after
  its LAST occurrence (the write schedule backward-fills, so output
  revisits stay consecutive — the TPU pipelining requirement — and the
  flush that lands carries the fully chained value).  The VMEM budget
  scales with the window (k + 2 rows/slab), NOT with N — the N=64
  two-slab config that blows the full-slab budget packs fine here.
  The hetero weighted hat needs sum_m w_jm v_m over ALL N slabs; the
  prefetch kernel splits it as base_j + sum_window w*(v - v_orig) with
  base_j = sum_m w_jm v_m^orig streamed per message (one N-pass outside
  the grid instead of N slabs resident per tile), which reorders the
  reduction — views agree to tolerance, state stays bit-exact.

Per-message scalars live in SMEM, where the kernel may index them by
the grid's message coordinate: a (5, k) f32 table — lr(t+j), gamma,
grad-coef, momentum-correction vscale, and the hat coefficient hc_j
(the send scale at the post-update step, which is where lr(t+j+1)
enters) — plus the (k,) int32 worker ids (the prefetch kernel reads its
window slots from the scalar-prefetch schedule instead).  A VMEM tile
indexed at a dynamic lane would not lower on the TPU.  Feeding the
schedule as per-message scalars is what lifts the constant-lr
restriction; hc_j is what generalizes the look-ahead beyond the v0
running sum:

    hat_mode "theta"      hat_j = theta'                  (plain senders)
    hat_mode "v0"         hat_j = theta' - hc_j*v0' [/den]  (dana/nadam)
    hat_mode "self"       hat_j = theta' - hc_j*v_i'        (lwp)
    hat_mode "weighted"   hat_j = theta' - hc_j*sum_m w_jm v_m'
                          (dana-hetero: the in-kernel weighted-slab
                          reduction; w rides in as a (k, N) SMEM table
                          and the N slab rows accumulate one by one)

The batched kernel covers exactly the ELEMENTWISE family (incl. delay
compensation and the weighted hat, which are elementwise per row).  The
gap-aware penalty needs a norm over every row of delta before any row
can be updated, then a second norm after — ``gap_master_update_1`` below
lowers ONE message as a two-phase grid (2, row_tiles): phase 0 sweeps
the row tiles accumulating ||theta - sent_i||^2 into SMEM scratch,
phase 1 re-sweeps applying the penalized update and accumulating
||v'||^2 for the avg_step EMA.  TPU pipelining only keeps output blocks
resident across CONSECUTIVE grid steps, so the k-message batch cannot
share one grid (message j+1's phase 0 would re-read tiles phase 1 just
wrote, a non-consecutive revisit); ``flat_master_update_batch_gap``
instead chains k two-phase calls inside one jit — the same k-rounds-in-
one-dispatch shape as PR 1's legacy kernel, which is inherent here: a
global reduction per message forces two full state sweeps per message
no matter how the grid is drawn.  The jnp reference (ref.py) stays the
cross-backend oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.flat import LANES, TILE_ROWS as BLOCK_ROWS
from .ref import default_hat_coefs

STAT_ROWS = 8              # gap kernel's (8, 128) avg_step output tile
# VMEM budget for the (N, block_rows, 128) slabs: in + out copies at 4
# bytes per slab, keep n_slabs * N * block_rows under ~8k rows (~8 MB).
_MAX_SLAB_ROWS = 8192


def _pick_block_rows(r: int, window: int, n_slabs: int = 1) -> int:
    """Largest row-tile size whose resident slab rows fit the VMEM
    budget.  ``window`` is the number of slab rows live per tile PER
    SLAB: the full-slab kernel passes N (every worker row streams), the
    prefetch kernel passes k + 2 (the k-slot scratch window plus the
    in/out blocks) — so its budget scales with the batch, never with
    the worker count.  The send kernel passes its slab height N.

    The tile is R itself when R fits the cap, else the largest multiple
    of 8 that divides R: the TPU lowering takes a block's second-minor
    dimension only as a multiple of 8 or the full dimension.  ``FlatSpec``
    pads R to a multiple of 8, and a state taller than one tile to a
    multiple of ``BLOCK_ROWS``, so its states always tile, and at full
    height wherever the cap is ``BLOCK_ROWS``."""
    cap = min(BLOCK_ROWS,
              (_MAX_SLAB_ROWS // max(window * n_slabs, 1)) // 8 * 8)
    if cap < 8:
        # even one 8-row tile of the resident slab rows would blow the
        # VMEM budget — don't silently lower an unloadable kernel
        raise ValueError(
            f"{window} resident slab rows x {n_slabs} slab(s) exceed "
            f"the batched kernel's VMEM slab budget ({_MAX_SLAB_ROWS} "
            f"rows); shard the master or use the tree path")
    if r <= cap:
        return r
    for d in range(cap, 7, -8):
        if r % d == 0:
            return d
    raise ValueError(
        f"{r} rows have no row tile that is a multiple of 8 and at most "
        f"{cap}; pad the rows to a multiple of 8 (FlatSpec does)")


def _smem():
    """Whole-array SMEM operand: per-message scalars the kernel indexes
    by a grid coordinate."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _scalar_table(lrs, gammas, cgs, vscales, hcs):
    """The (5, k) f32 per-message scalar table (rows: lr, gamma, cg,
    vscale, hc)."""
    return jnp.stack([jnp.asarray(x, jnp.float32)
                      for x in (lrs, gammas, cgs, vscales, hcs)])


def _make_kernel(nesterov: bool, track_v0: bool, adaptive: bool,
                 track_sent: bool, b2: float, eps: float,
                 dc_lambda: float | None, sent_view: bool,
                 hat_mode: str, telemetry: bool, n_workers: int):
    def kernel(*refs):
        it = iter(refs)
        ids_ref, scal_ref = next(it), next(it)
        w_ref = next(it) if hat_mode == "weighted" else None
        theta_ref, v_ref = next(it), next(it)
        v0_ref = next(it) if track_v0 else None
        u2_ref = next(it) if adaptive else None
        sent_ref = next(it) if track_sent else None
        g_ref = next(it)
        theta_o, v_o = next(it), next(it)
        v0_o = next(it) if track_v0 else None
        u2_o = next(it) if adaptive else None
        sent_o = next(it) if track_sent else None
        hat_o = next(it)
        pre_o = next(it) if telemetry else None

        j = pl.program_id(1)
        i = ids_ref[j]
        lr = scal_ref[0, j]
        gamma = scal_ref[1, j]
        cg = scal_ref[2, j]
        vs = scal_ref[3, j]
        hc = scal_ref[4, j]

        @pl.when(j == 0)
        def _seed_state():
            theta_o[...] = theta_ref[...]
            v_o[...] = v_ref[...]
            if track_v0:
                v0_o[...] = v0_ref[...]
            if adaptive:
                u2_o[...] = u2_ref[...]
            if track_sent:
                sent_o[...] = sent_ref[...]

        theta = theta_o[...]
        if telemetry:
            pre_o[...] = theta[None]            # theta BEFORE message j
        gj = g_ref[...][0]                       # (block_r, 128)
        vi = v_o[pl.ds(i, 1), :, :][0]           # dynamic worker row
        if track_sent:
            si = sent_o[pl.ds(i, 1), :, :][0]
            delta = theta - si
            if dc_lambda is not None:
                gj = gj + dc_lambda * ((gj * gj) * delta)
        v_new = gamma * vi + cg * ((1.0 / vs) * gj)
        if adaptive:
            u2 = b2 * u2_o[...] + (1 - b2) * gj * gj
            u2_o[...] = u2
            denom = jnp.sqrt(u2) + eps
        if nesterov:
            num = (gamma * vs) * v_new + cg * gj
            if adaptive:
                theta = (-lr) * (num / denom) + theta
            else:
                theta = (-lr) * num + theta
        else:
            if adaptive:
                theta = ((-lr) * vs) * (v_new / denom) + theta
            else:
                theta = ((-lr) * vs) * v_new + theta
        theta_o[...] = theta
        # the slab row updates BEFORE the hat: the weighted hat reduces
        # over the post-update slab (message j+1 then chains on it too)
        v_o[pl.ds(i, 1), :, :] = v_new[None]
        if track_v0:
            v0 = (v0_o[...] - vi) + v_new
            v0_o[...] = v0
        if hat_mode == "theta":
            hat = theta
        elif hat_mode == "v0":
            if adaptive:
                hat = theta - (hc * v0) / denom
            else:
                hat = (-hc) * v0 + theta
        elif hat_mode == "self":
            hat = (-hc) * v_new + theta
        else:                                    # "weighted"
            wsum = w_ref[j, 0] * v_o[0]
            for m in range(1, n_workers):
                wsum = wsum + w_ref[j, m] * v_o[m]
            hat = (-hc) * wsum + theta
        hat_o[...] = hat[None]
        if track_sent:
            sent_o[pl.ds(i, 1), :, :] = (hat if sent_view else theta)[None]

    return kernel


@functools.partial(
    jax.jit, static_argnames=("nesterov", "b2", "eps", "dc_lambda",
                              "sent_view", "hat_mode", "telemetry",
                              "interpret"))
def flat_master_update_batch_2d(theta, v, v0, u2, sent, g, ids, lrs,
                                lrs_next, gammas, cgs, vscales, *,
                                nesterov: bool, b2: float = 0.999,
                                eps: float = 1e-8,
                                dc_lambda: float | None = None,
                                sent_view: bool = False,
                                hat_mode: str | None = None,
                                hcs=None, weights=None,
                                telemetry: bool = False,
                                interpret: bool = False):
    """Batched flat master update (see ref.py for the update rule; this
    lowering covers the elementwise family — no gap-aware penalty).

    theta (R,128); v (N,R,128); v0/u2 (R,128) or None; sent (N,R,128) or
    None; g (k,R,128); ids/lrs/lrs_next/gammas/cgs/vscales (k,); hcs
    (k,) hat coefficients or None (legacy v0 look-ahead scale); weights
    (k, N) rate weights for hat_mode "weighted".
    Returns (theta', v', v0', u2', sent', hats, thetas_pre or None).
    """
    r, lanes = theta.shape
    n = v.shape[0]
    k = g.shape[0]
    assert lanes == LANES, f"lane dim must be {LANES}, got {lanes}"
    track_v0 = v0 is not None
    adaptive = u2 is not None
    track_sent = sent is not None
    if hat_mode is None:
        hat_mode = "v0" if track_v0 else "theta"
    if hcs is None:
        hcs = default_hat_coefs(lrs_next, gammas, vscales,
                                adaptive=adaptive)
    block_r = _pick_block_rows(r, n, 2 if track_sent else 1)
    assert r % block_r == 0, (r, block_r)
    grid = (r // block_r, k)

    # lrs_next itself never enters the kernel: its only consumer is the
    # hat coefficient, folded into hcs above
    scal = _scalar_table(lrs, gammas, cgs, vscales, hcs)

    flat_spec = pl.BlockSpec((block_r, LANES), lambda ri, j: (ri, 0))
    slab_spec = pl.BlockSpec((n, block_r, LANES), lambda ri, j: (0, ri, 0))
    msg_spec = pl.BlockSpec((1, block_r, LANES), lambda ri, j: (j, ri, 0))

    f32 = jnp.float32
    in_specs = [_smem(), _smem()]
    inputs = [jnp.asarray(ids, jnp.int32), scal]
    if hat_mode == "weighted":
        in_specs.append(_smem())
        inputs.append(jnp.asarray(weights, f32))
    # state inputs alias their outputs: with donated caller buffers the
    # batch updates the master state in place (no-copy tested)
    aliases = {len(inputs): 0}
    in_specs.append(flat_spec)
    inputs.append(theta)
    aliases[len(inputs)] = 1
    in_specs.append(slab_spec)
    inputs.append(v)
    out_specs = [flat_spec, slab_spec]
    out_shape = [jax.ShapeDtypeStruct((r, LANES), f32),
                 jax.ShapeDtypeStruct((n, r, LANES), f32)]
    if track_v0:
        aliases[len(inputs)] = len(out_specs)
        in_specs.append(flat_spec)
        inputs.append(v0)
        out_specs.append(flat_spec)
        out_shape.append(jax.ShapeDtypeStruct((r, LANES), f32))
    if adaptive:
        aliases[len(inputs)] = len(out_specs)
        in_specs.append(flat_spec)
        inputs.append(u2)
        out_specs.append(flat_spec)
        out_shape.append(jax.ShapeDtypeStruct((r, LANES), f32))
    if track_sent:
        aliases[len(inputs)] = len(out_specs)
        in_specs.append(slab_spec)
        inputs.append(sent)
        out_specs.append(slab_spec)
        out_shape.append(jax.ShapeDtypeStruct((n, r, LANES), f32))
    in_specs.append(msg_spec)
    inputs.append(g)
    out_specs.append(msg_spec)
    out_shape.append(jax.ShapeDtypeStruct((k, r, LANES), f32))
    if telemetry:
        out_specs.append(msg_spec)
        out_shape.append(jax.ShapeDtypeStruct((k, r, LANES), f32))

    outs = pl.pallas_call(
        _make_kernel(nesterov, track_v0, adaptive, track_sent, b2, eps,
                     dc_lambda, sent_view, hat_mode, telemetry, n),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*inputs)

    it = iter(outs)
    theta_n, v_n = next(it), next(it)
    v0_n = next(it) if track_v0 else None
    u2_n = next(it) if adaptive else None
    sent_n = next(it) if track_sent else None
    hats = next(it)
    pres = next(it) if telemetry else None
    return theta_n, v_n, v0_n, u2_n, sent_n, hats, pres


# ---------------------------------------------------------------------------
# scalar-prefetch memory tier: DMA only the touched worker slabs
# ---------------------------------------------------------------------------
def _prefetch_schedule(ids, k: int):
    """The (5, k) int32 scalar-prefetch schedule for a batch of worker
    ids (duplicates allowed):

      row 0  fetch block index — forward-filled first-occurrence ids, so
             a duplicate step keeps the previous block index and the
             pipeline never re-fetches a row the window already owns;
      row 1  write block index — backward-filled last-occurrence ids, so
             each touched row's output blocks are revisited CONSECUTIVELY
             and the flush that lands (at its last occurrence) carries
             the fully chained value;
      row 2  window slot of message j (its id's first-occurrence index);
      row 3  window slot owning this step's write block;
      row 4  1 iff j is its id's first occurrence (gate the window load).
    """
    idx = jnp.arange(k, dtype=jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)
    eq = ids[:, None] == ids[None, :]
    pos = jnp.argmax(eq, axis=1).astype(jnp.int32)        # first occurrence
    is_first = pos == idx
    last = (k - 1) - jnp.argmax(eq[:, ::-1], axis=1).astype(jnp.int32)
    lastmark = jnp.where(last == idx, idx, k)
    # rev_min[j] = min{m >= j : m is a last occurrence}; always defined
    # (index k-1 is its own id's last occurrence)
    rev_min = jax.lax.associative_scan(jnp.minimum, lastmark[::-1])[::-1]
    canon = jnp.where(is_first, idx, -1)
    canon_ff = jax.lax.associative_scan(jnp.maximum, canon)
    return jnp.stack([ids[canon_ff], ids[rev_min], pos, pos[rev_min],
                      is_first.astype(jnp.int32)])


def _make_prefetch_kernel(nesterov: bool, track_v0: bool, adaptive: bool,
                          track_sent: bool, b2: float, eps: float,
                          dc_lambda: float | None, sent_view: bool,
                          hat_mode: str, telemetry: bool, n_window: int):
    weighted = hat_mode == "weighted"

    def kernel(*refs):
        it = iter(refs)
        sched_ref = next(it)                     # scalar prefetch (SMEM)
        scal_ref = next(it)
        ww_ref = next(it) if weighted else None
        theta_ref, v_ref = next(it), next(it)
        v0_ref = next(it) if track_v0 else None
        u2_ref = next(it) if adaptive else None
        sent_ref = next(it) if track_sent else None
        base_ref = next(it) if weighted else None
        g_ref = next(it)
        theta_o, v_o = next(it), next(it)
        v0_o = next(it) if track_v0 else None
        u2_o = next(it) if adaptive else None
        sent_o = next(it) if track_sent else None
        hat_o = next(it)
        pre_o = next(it) if telemetry else None
        v_scr = next(it)                         # (k, block_r, 128) VMEM
        sent_scr = next(it) if track_sent else None
        orig_scr = next(it) if weighted else None

        j = pl.program_id(1)
        slot = sched_ref[2, j]
        wslot = sched_ref[3, j]
        lr = scal_ref[0, j]
        gamma = scal_ref[1, j]
        cg = scal_ref[2, j]
        vs = scal_ref[3, j]
        hc = scal_ref[4, j]

        @pl.when(j == 0)
        def _seed_state():
            theta_o[...] = theta_ref[...]
            if track_v0:
                v0_o[...] = v0_ref[...]
            if adaptive:
                u2_o[...] = u2_ref[...]
            if weighted:
                # the weighted hat reduces over EVERY window slot; slots
                # no message ever claims must read as zero deltas
                v_scr[...] = jnp.zeros_like(v_scr)
                orig_scr[...] = jnp.zeros_like(orig_scr)

        @pl.when(sched_ref[4, j] == 1)
        def _load_window():
            # first occurrence of this id: pull its slab row into the
            # window (the fetch schedule guarantees v_ref holds it here)
            v_scr[pl.ds(slot, 1), :, :] = v_ref[...]
            if track_sent:
                sent_scr[pl.ds(slot, 1), :, :] = sent_ref[...]
            if weighted:
                orig_scr[pl.ds(slot, 1), :, :] = v_ref[...]

        theta = theta_o[...]
        if telemetry:
            pre_o[...] = theta[None]            # theta BEFORE message j
        gj = g_ref[...][0]                       # (block_r, 128)
        vi = v_scr[pl.ds(slot, 1), :, :][0]      # windowed worker row
        if track_sent:
            si = sent_scr[pl.ds(slot, 1), :, :][0]
            delta = theta - si
            if dc_lambda is not None:
                gj = gj + dc_lambda * ((gj * gj) * delta)
        v_new = gamma * vi + cg * ((1.0 / vs) * gj)
        if adaptive:
            u2 = b2 * u2_o[...] + (1 - b2) * gj * gj
            u2_o[...] = u2
            denom = jnp.sqrt(u2) + eps
        if nesterov:
            num = (gamma * vs) * v_new + cg * gj
            if adaptive:
                theta = (-lr) * (num / denom) + theta
            else:
                theta = (-lr) * num + theta
        else:
            if adaptive:
                theta = ((-lr) * vs) * (v_new / denom) + theta
            else:
                theta = ((-lr) * vs) * v_new + theta
        theta_o[...] = theta
        # window slot updates BEFORE the hat (the weighted hat reduces
        # over the post-update window; message j+1 chains on it too)
        v_scr[pl.ds(slot, 1), :, :] = v_new[None]
        if track_v0:
            v0 = (v0_o[...] - vi) + v_new
            v0_o[...] = v0
        if hat_mode == "theta":
            hat = theta
        elif hat_mode == "v0":
            if adaptive:
                hat = theta - (hc * v0) / denom
            else:
                hat = (-hc) * v0 + theta
        elif hat_mode == "self":
            hat = (-hc) * v_new + theta
        else:                                    # "weighted"
            # base_j + the window's deltas, one slot at a time
            wsum = base_ref[...][0]
            for m in range(n_window):
                wsum = wsum + ww_ref[j, m] * (v_scr[m] - orig_scr[m])
            hat = (-hc) * wsum + theta
        hat_o[...] = hat[None]
        if track_sent:
            sent_scr[pl.ds(slot, 1), :, :] = \
                (hat if sent_view else theta)[None]
        # stream the window slot that owns this step's output block
        v_o[...] = v_scr[pl.ds(wslot, 1), :, :]
        if track_sent:
            sent_o[...] = sent_scr[pl.ds(wslot, 1), :, :]

    return kernel


@functools.partial(
    jax.jit, static_argnames=("nesterov", "b2", "eps", "dc_lambda",
                              "sent_view", "hat_mode", "telemetry",
                              "interpret"))
def flat_master_update_batch_prefetch(theta, v, v0, u2, sent, g, ids, lrs,
                                      lrs_next, gammas, cgs, vscales, *,
                                      nesterov: bool, b2: float = 0.999,
                                      eps: float = 1e-8,
                                      dc_lambda: float | None = None,
                                      sent_view: bool = False,
                                      hat_mode: str | None = None,
                                      hcs=None, weights=None,
                                      telemetry: bool = False,
                                      interpret: bool = False):
    """Batched flat master update, scalar-prefetch memory tier: same
    contract as ``flat_master_update_batch_2d`` (bit-exact for every
    non-weighted hat; the weighted view agrees to reduction-order
    tolerance) but slab traffic is 2u streams for u unique senders and
    the VMEM budget is independent of N."""
    r, lanes = theta.shape
    n = v.shape[0]
    k = g.shape[0]
    assert lanes == LANES, f"lane dim must be {LANES}, got {lanes}"
    track_v0 = v0 is not None
    adaptive = u2 is not None
    track_sent = sent is not None
    if hat_mode is None:
        hat_mode = "v0" if track_v0 else "theta"
    weighted = hat_mode == "weighted"
    if hcs is None:
        hcs = default_hat_coefs(lrs_next, gammas, vscales,
                                adaptive=adaptive)
    # resident slab rows per tile: the k-slot window (+1 orig window in
    # weighted mode) plus one in + one out block — never N
    block_r = _pick_block_rows(
        r, k + 2 + (k if weighted else 0), 2 if track_sent else 1)
    assert r % block_r == 0, (r, block_r)
    grid = (r // block_r, k)

    sched = _prefetch_schedule(ids, k)
    scal = _scalar_table(lrs, gammas, cgs, vscales, hcs)

    # index maps see the grid indices then the scalar-prefetch ref: the
    # slab specs pick ONE worker row per step from the schedule
    flat_spec = pl.BlockSpec((block_r, LANES), lambda ri, j, s: (ri, 0))
    slab_in = pl.BlockSpec((1, block_r, LANES),
                           lambda ri, j, s: (s[0, j], ri, 0))
    slab_out = pl.BlockSpec((1, block_r, LANES),
                            lambda ri, j, s: (s[1, j], ri, 0))
    msg_spec = pl.BlockSpec((1, block_r, LANES),
                            lambda ri, j, s: (j, ri, 0))

    f32 = jnp.float32
    in_specs = [_smem()]
    inputs = [sched, scal]                        # sched counts in aliases
    if weighted:
        w = jnp.asarray(weights, f32)
        # window weights ww[j, s] = w[j, ids[s]], zeroed off-canonical
        # slots; base_j = sum_m w[j, m] v_m^orig streamed per message
        ww = jnp.take(w, jnp.asarray(ids, jnp.int32), axis=1) \
            * sched[4].astype(f32)[None, :]
        base = jnp.tensordot(w, v, axes=([1], [0]))
        in_specs.append(_smem())
        inputs.append(ww)
    # state inputs alias their outputs: with donated caller buffers the
    # batch updates in place, and slab blocks no schedule entry ever
    # writes KEEP their input rows — that is what makes 2u-stream slab
    # I/O correct for the N - u untouched workers
    aliases = {len(inputs): 0}
    in_specs.append(flat_spec)
    inputs.append(theta)
    aliases[len(inputs)] = 1
    in_specs.append(slab_in)
    inputs.append(v)
    out_specs = [flat_spec, slab_out]
    out_shape = [jax.ShapeDtypeStruct((r, LANES), f32),
                 jax.ShapeDtypeStruct((n, r, LANES), f32)]
    if track_v0:
        aliases[len(inputs)] = len(out_specs)
        in_specs.append(flat_spec)
        inputs.append(v0)
        out_specs.append(flat_spec)
        out_shape.append(jax.ShapeDtypeStruct((r, LANES), f32))
    if adaptive:
        aliases[len(inputs)] = len(out_specs)
        in_specs.append(flat_spec)
        inputs.append(u2)
        out_specs.append(flat_spec)
        out_shape.append(jax.ShapeDtypeStruct((r, LANES), f32))
    if track_sent:
        aliases[len(inputs)] = len(out_specs)
        in_specs.append(slab_in)
        inputs.append(sent)
        out_specs.append(slab_out)
        out_shape.append(jax.ShapeDtypeStruct((n, r, LANES), f32))
    if weighted:
        in_specs.append(msg_spec)
        inputs.append(base)
    in_specs.append(msg_spec)
    inputs.append(g)
    out_specs.append(msg_spec)
    out_shape.append(jax.ShapeDtypeStruct((k, r, LANES), f32))
    if telemetry:
        out_specs.append(msg_spec)
        out_shape.append(jax.ShapeDtypeStruct((k, r, LANES), f32))

    scratch = [pltpu.VMEM((k, block_r, LANES), f32)]
    if track_sent:
        scratch.append(pltpu.VMEM((k, block_r, LANES), f32))
    if weighted:
        scratch.append(pltpu.VMEM((k, block_r, LANES), f32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch)
    outs = pl.pallas_call(
        _make_prefetch_kernel(nesterov, track_v0, adaptive, track_sent,
                              b2, eps, dc_lambda, sent_view, hat_mode,
                              telemetry, k),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*inputs)

    it = iter(outs)
    theta_n, v_n = next(it), next(it)
    v0_n = next(it) if track_v0 else None
    u2_n = next(it) if adaptive else None
    sent_n = next(it) if track_sent else None
    hats = next(it)
    pres = next(it) if telemetry else None
    return theta_n, v_n, v0_n, u2_n, sent_n, hats, pres


# ---------------------------------------------------------------------------
# gap-aware: two-phase reduce-then-apply lowering
# ---------------------------------------------------------------------------
def gap_pallas_supported(rows: int, n: int, prefetch: bool = False) -> bool:
    """The two-phase grid needs >= 2 row tiles: with a single tile the
    phase-0 and phase-1 flushes of the same output block are issued
    back-to-back from different pipeline slots and may race on HBM.
    Tiny states fall back to the jnp reference (which is fast there).
    The prefetch variant holds ONE worker row per slab (scalar-prefetch
    block selection), so its budget — like the batched kernel's — is
    independent of N."""
    try:
        block_r = _pick_block_rows(rows, 3 if prefetch else n, 2)
    except ValueError:
        return False
    return rows // block_r >= 2


def _make_gap_kernel(gap_ema: float, sqrt_p: float, telemetry: bool,
                     prefetch: bool):
    def kernel(*refs):
        it = iter(refs)
        ids_ref, scal_ref = next(it), next(it)   # ids: scalar prefetch
        theta_ref, v_ref, sent_ref, g_ref = (next(it), next(it), next(it),
                                             next(it))
        theta_o, v_o, sent_o, hat_o, stat_o = (
            next(it), next(it), next(it), next(it), next(it))
        pre_o = next(it) if telemetry else None
        acc = next(it)                           # SMEM (4,): gap2, vn2, avg

        ph = pl.program_id(0)
        ri = pl.program_id(1)
        nt = pl.num_programs(1)
        i = ids_ref[0]
        lr = scal_ref[0]
        gamma = scal_ref[1]
        cg = scal_ref[2]
        vs = scal_ref[3]

        @pl.when((ph == 0) & (ri == 0))
        def _seed():
            acc[0] = 0.0
            acc[1] = 0.0
            acc[2] = scal_ref[4]                 # avg_step in

        theta = theta_ref[...]
        # prefetch: the slab blocks ARE worker i's row (scalar-prefetch
        # index maps); full slab: dynamic-slice it out
        si = (sent_ref[...] if prefetch
              else sent_ref[pl.ds(i, 1), :, :])[0]

        @pl.when(ph == 0)
        def _reduce():
            # pass 1: accumulate ||theta - sent_i||^2 across row tiles;
            # outputs get a passthrough write so every flush carries
            # valid data (phase 1 overwrites the same blocks)
            d = theta - si
            acc[0] = acc[0] + jnp.sum(d * d)
            theta_o[...] = theta
            v_o[...] = v_ref[...]
            sent_o[...] = sent_ref[...]
            hat_o[...] = theta
            if telemetry:
                pre_o[...] = theta

        @pl.when(ph == 1)
        def _apply():
            # pass 2: the penalized family update per tile, accumulating
            # ||v'||^2 for the avg_step EMA as it goes
            gap = jnp.sqrt(acc[0]) / sqrt_p
            penalty = 1.0 + gap / jnp.maximum(acc[2], 1e-12)
            gj = (1.0 / penalty) * g_ref[...]
            vi = (v_ref[...] if prefetch
                  else v_ref[pl.ds(i, 1), :, :])[0]
            v_new = gamma * vi + cg * ((1.0 / vs) * gj)
            th = ((-lr) * vs) * v_new + theta
            theta_o[...] = th
            hat_o[...] = th
            if prefetch:
                v_o[...] = v_new[None]
                sent_o[...] = th[None]
            else:
                v_o[...] = v_ref[...]
                v_o[pl.ds(i, 1), :, :] = v_new[None]
                sent_o[...] = sent_ref[...]
                sent_o[pl.ds(i, 1), :, :] = th[None]
            if telemetry:
                # every phase's visit must write (the phase-1 flush is
                # the one that lands); theta here is the pre-update input
                pre_o[...] = theta
            acc[1] = acc[1] + jnp.sum(v_new * v_new)

            @pl.when(ri == nt - 1)
            def _finish():
                step_rms = lr * vs * jnp.sqrt(acc[1]) / sqrt_p
                avg = gap_ema * acc[2] + (1 - gap_ema) * step_rms
                acc[2] = avg
                stat_o[...] = jnp.full(stat_o.shape, avg, jnp.float32)

    return kernel


def gap_master_update_1(theta, v, sent, avg_step, g_row, i, lr, gamma,
                        cg, vs, *, gap_ema: float, n_elems: int,
                        telemetry: bool, interpret: bool,
                        prefetch: bool = False):
    """ONE gap-aware message, grid (2, row_tiles) with SMEM-scratch
    norm partials.  Returns (theta', v', sent', avg_step', hat, pre).

    Worker i's id rides in as the scalar-prefetch operand.  ``prefetch``
    selects worker i's v/sent rows through it (one-row slab blocks,
    N-independent VMEM) and aliases the state inputs to their outputs —
    untouched workers' rows survive through the aliasing instead of
    full-slab passthrough writes."""
    r, lanes = theta.shape
    n = v.shape[0]
    assert lanes == LANES, lanes
    block_r = _pick_block_rows(r, 3 if prefetch else n, 2)
    nt = r // block_r
    grid = (2, nt)
    # f32-rounded like the reference's jnp.sqrt(asarray(n_elems, f32))
    sqrt_p = float(np.sqrt(np.float32(n_elems), dtype=np.float32))
    scal = jnp.stack([jnp.asarray(x, jnp.float32)
                      for x in (lr, gamma, cg, vs, avg_step)])
    ids = jnp.reshape(jnp.asarray(i, jnp.int32), (1,))

    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct((r, LANES), f32),
                 jax.ShapeDtypeStruct((n, r, LANES), f32),
                 jax.ShapeDtypeStruct((n, r, LANES), f32),
                 jax.ShapeDtypeStruct((r, LANES), f32),
                 jax.ShapeDtypeStruct((STAT_ROWS, LANES), f32)]
    if telemetry:
        out_shape.append(jax.ShapeDtypeStruct((r, LANES), f32))
    flat_spec = pl.BlockSpec((block_r, LANES), lambda ph, ri, s: (ri, 0))
    if prefetch:
        slab_spec = pl.BlockSpec((1, block_r, LANES),
                                 lambda ph, ri, s: (s[0], ri, 0))
        # theta/v/sent alias their outputs (operand indices count the
        # scalar-prefetch ref)
        aliases = {2: 0, 3: 1, 4: 2}
    else:
        slab_spec = pl.BlockSpec((n, block_r, LANES),
                                 lambda ph, ri, s: (0, ri, 0))
        aliases = {}
    stat_spec = pl.BlockSpec((STAT_ROWS, LANES), lambda ph, ri, s: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[_smem(), flat_spec, slab_spec, slab_spec, flat_spec],
        out_specs=[flat_spec, slab_spec, slab_spec, flat_spec, stat_spec]
        + ([flat_spec] if telemetry else []),
        scratch_shapes=[pltpu.SMEM((4,), f32)])
    out = pl.pallas_call(
        _make_gap_kernel(gap_ema, sqrt_p, telemetry, prefetch),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(ids, scal, theta, v, sent, g_row)
    theta_n, v_n, sent_n, hat, stat = out[:5]
    pre = out[5] if telemetry else None
    return theta_n, v_n, sent_n, stat[0, 0], hat, pre


@functools.partial(
    jax.jit, static_argnames=("gap_ema", "n_elems", "telemetry",
                              "interpret", "prefetch"))
def flat_master_update_batch_gap(theta, v, sent, avg_step, g, ids, lrs,
                                 gammas, cgs, vscales, *, gap_ema: float,
                                 n_elems: int, telemetry: bool = False,
                                 interpret: bool = False,
                                 prefetch: bool = False):
    """k gap-aware messages: k chained two-phase kernels in one jit
    (see module docstring for why the messages cannot share one grid).
    Returns (theta', v', sent', avg_step', hats, pres or None)."""
    k = g.shape[0]
    hats, pres = [], []
    for j in range(k):
        theta, v, sent, avg_step, hat, pre = gap_master_update_1(
            theta, v, sent, avg_step, g[j], ids[j], lrs[j], gammas[j],
            cgs[j], vscales[j], gap_ema=gap_ema, n_elems=n_elems,
            telemetry=telemetry, interpret=interpret, prefetch=prefetch)
        hats.append(hat)
        if telemetry:
            pres.append(pre)
    return (theta, v, sent, avg_step, jnp.stack(hats),
            jnp.stack(pres) if telemetry else None)
