"""Row-sharded multi-master parameter server on the flat layout.

The paper attributes its scaling ceiling to the single parameter server
(App. C.1): above ~20 workers the master, not the network, bounds
throughput.  PR 2's flat ``(R, 128)`` layout makes the obvious fix cheap:
every update rule in the kernel-eligible family is elementwise per row,
so the SAME flat buffers split into S contiguous row ranges
(``FlatSpec.row_ranges``) and S independent shard servers — one serving
thread + one coalesced ``flat_update`` pass per shard — apply each
worker message to only their rows.  Concatenating the shard states in
range order reconstructs the single-master state *bit-for-bit* whenever
the shards apply the same message sequence (deterministic mode always;
tested), which is the claim that lets asynchronous momentum methods keep
scaling where a single server saturates.

Protocol: workers push a gradient ONCE — their grad jit packs it flat
and scatters it into per-shard row slices (``FanoutMailbox`` fans the
message out atomically, ``_ReplyGroup`` gathers the S view slices back
into one reply).  Shard clocks are barrier-free: each shard server
drains its own mailbox at its own pace and advances its own step counter
with no cross-shard synchronization on the hot path.  Because the
fan-out is atomic and each shard's queue is FIFO, every shard still
applies the identical message sequence (and, at end-of-run truncation,
the identical message SET) — per-shard reorder *injection* is the only
thing that makes shard orders diverge.  In deterministic mode the
virtual clock serializes pushes and the run replays the engine exactly.

Cross-shard aggregation happens OFF the hot path:

* telemetry — each shard contributes its rows' partial ``sum d^2`` /
  ``sum g^2``; the gap/grad-norm row is recorded once all S partials for
  a message are in (shard 0 carries step/lag/time).
* eval — each shard snapshots its theta slice when ITS applied count
  crosses an eval boundary; the eval runs on the assembled full vector
  once all S slices for that boundary exist.  The shared serve loop
  never lets a fused chunk straddle an eval boundary, so every shard
  snapshots the state at EXACTLY the same applied-count watermark even
  when their drain batches differ (in deterministic mode this is
  exactly the engine's eval point; under reorder injection the orders
  may differ but the message SET at the watermark is identical).

One family member needs cross-shard data ON the hot path: gap-aware
(ga-asgd) scales each gradient by the norm of ``theta - sent_i`` over
ALL rows.  Its shards drain real coalesced batches and stream each
message's two scalars (the gap partial ``sum d^2`` before applying, the
update-norm partial for the ``avg_step`` EMA after) through a lock-free
``_NormExchange`` ring — one blocking rendezvous per drained batch in
the balanced steady state, not two per message (the PR-4 coalesce=1
clamp is gone).  Every shard sees the identical combined norms, so
their scalar trajectories stay equal — but the partial-sum reduction
order differs from the single master's full-buffer sum, so sharded
gap-aware matches the single flat master to float tolerance, not
bit-exactly (the elementwise family stays bit-exact; see
``eligibility_matrix``).  The rate-weighted member (dana-hetero) needs
no exchange at all: its weighted send reduces per row, and the rate
lane replicates per shard through the existing copied-scalar path
(every shard sees every message with the same timestamp).

Fault injection is per shard: each server owns a ``FaultInjector`` with
a shard-seeded reorder substream (``FaultPlan.reorder_shards`` confines
reordering to chosen shards), so a fault on one shard's link leaves the
other shards' replay bit-for-bit unchanged (tested).
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.algorithms import Algorithm
from ..core.metrics import History
from ..kernels.flat_update import (FlatAlgorithm, kernel_eligible,
                                   merge_flat, slice_flat, unpack_state)
from .faults import FaultInjector
from .mailbox import FanoutMailbox, GradMsg, Mailbox, Reply
from .master import run_serve_loop


class _NormExchange:
    """Cross-shard scalar-sum exchange for the gap-aware hot path.

    Each message needs two shard-ordered f32 sums: phase 0 the gap
    partials ``sum d^2`` (before any shard may apply), phase 1 the
    update-norm partials ``||v'||^2`` (before the avg_step EMA).  PR 4
    ran one condition-variable rendezvous per scalar — two lock +
    notify_all round trips per message — and clamped the gap-aware
    shards to coalesce=1.  The exchange is now a preallocated ring:
    shard ``sid`` publishes its partial for (seq, phase) with a
    GIL-atomic numpy store (value first, generation stamp second, so a
    reader that sees the stamp sees the value) and reads peers back
    with a bounded spin.  Message sequence is identical across shards
    (the fan-out is atomic FIFO) and a shard cannot run ahead of its
    peers by more than one message (it needs THEIR partials to finish
    seq before publishing seq+1), so intra-batch totals stream through
    the ring without any lock — shards working through the same drained
    batch meet each other's values already published.  Only when a peer
    genuinely falls behind (batch boundaries misaligned, scheduler
    hiccup) does the reader fall back to a sleeping wait: one blocking
    rendezvous per drained batch in the balanced steady state, instead
    of 2k.  Every shard computes the SAME shard-ordered f32 sum, so
    downstream scalar trajectories (penalty, avg_step) stay
    bit-identical to each other.  Stop-aware: a cluster shutdown aborts
    waiters instead of hanging them."""

    WINDOW = 256          # ring depth (skew is <= 1 message, see above)
    SPINS = 2000          # GIL-yield spins before the sleeping fallback

    def __init__(self, shards: int, stop: threading.Event):
        self.shards = shards
        self.stop = stop
        self.vals = np.zeros((self.WINDOW, 2, shards), np.float32)
        self.gen = np.zeros((self.WINDOW, 2, shards), np.int64)

    def combine(self, sid: int, seq: int, phase: int,
                partial: float) -> float:
        slot = seq % self.WINDOW
        g = seq // self.WINDOW + 1
        self.vals[slot, phase, sid] = np.float32(partial)
        self.gen[slot, phase, sid] = g          # publish AFTER the value
        row = self.gen[slot, phase]
        spins = 0
        while not (row >= g).all():
            spins += 1
            if spins <= self.SPINS:
                time.sleep(0)                   # yield the GIL
            else:
                if self.stop.is_set():
                    raise RuntimeError(
                        "norm exchange aborted: cluster stopping")
                time.sleep(5e-5)
        total = np.float32(0.0)                 # f32, shard order: every
        for s in range(self.shards):            # shard computes the same
            total = np.float32(total + self.vals[slot, phase, s])
        return float(total)


class RowRebalancer:
    """Online row-range rebalancing between adjacent shards.

    Every ``every`` applied messages (the eval watermarks — the shared
    serve loop already guarantees no fused chunk straddles them, so all
    S shards pause at EXACTLY the same applied count) the first shard to
    reach the watermark reads the per-shard ``busy_s`` gauges — through
    ``SnapshotPublisher.series()`` when the observability layer is wired,
    the live gauges otherwise — and decides at most ONE boundary shift:
    the busiest shard donates a row-aligned block from the edge adjacent
    to its least-busy neighbor.  The decision is cached per watermark, so
    every shard sees the identical plan; the donor slices the rows off
    its state (``slice_flat``) and publishes them in a rendezvous slot,
    the receiver blocks until they arrive and concatenates
    (``merge_flat``).  Because the fan-out delivers every message to
    every shard and the family is elementwise per row, WHERE a row lives
    never changes its arithmetic — the reassembled final state is
    bit-identical to the unrebalanced run (tested), the PR-4
    exact-applied-count watermark is what makes the handoff
    torn-state-free.  Shards not named in the plan pass straight
    through (their rows are untouched).  Gap-aware is excluded (its
    cross-shard norm exchange assumes fixed ranges)."""

    def __init__(self, owner: "ShardedMaster", *, every: int,
                 threshold: float = 1.1, series_fn=None):
        self.owner = owner
        self.every = max(1, every)
        self.threshold = float(threshold)
        self.series_fn = series_fn          # SnapshotPublisher.series
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._plans: dict = {}              # watermark -> plan | None
        self._pieces: dict = {}             # watermark -> donated rows
        self.moves: list[tuple] = []        # (watermark, donor, recv, n)

    # -- decision ---------------------------------------------------------
    def _busy(self) -> list[float]:
        busy = [float(srv.busy_s) for srv in self.owner.shards_]
        if self.series_fn is not None:
            try:
                series = self.series_fn()
                for s in range(len(busy)):
                    pts = series.get(f"busy_s/shard{s}")
                    if pts:
                        busy[s] = float(pts[-1][1])
            except Exception:  # noqa: BLE001 - observation must not kill
                pass
        return busy

    def _decide(self):
        srvs = self.owner.shards_
        busy = self._busy()
        donor = max(range(len(busy)), key=lambda s: busy[s])
        cands = [s for s in (donor - 1, donor + 1) if 0 <= s < len(busy)]
        recv = min(cands, key=lambda s: busy[s])
        if busy[donor] < self.threshold * max(busy[recv], 1e-12):
            return None
        align = self.owner.spec.row_align
        rows_d = srvs[donor].r1 - srvs[donor].r0
        rows_r = srvs[recv].r1 - srvs[recv].r0
        # shift a quarter of the row imbalance, row-aligned, and leave
        # the donor at least one aligned block (no empty shards)
        move = max((rows_d - rows_r) // 4 // align * align, 0)
        move = min(move, (rows_d - align) // align * align)
        if move < align:
            return None
        return (donor, recv, move)

    def _plan_for(self, wm: int):
        with self._lock:
            if wm not in self._plans:
                self._plans[wm] = self._decide()
            return self._plans[wm]

    # -- rendezvous -------------------------------------------------------
    def at_watermark(self, srv: "_ShardServer"):
        wm = srv.applied
        if wm % self.every or wm >= self.owner.total:
            return
        plan = self._plan_for(wm)
        if plan is None:
            return
        donor, recv, move = plan
        if srv.sid == donor:
            self._donate(srv, wm, recv, move)
        elif srv.sid == recv:
            self._receive(srv, wm, donor, move)

    def _donate(self, srv, wm, recv, move):
        # re-clamp against the donor's rows AT EXECUTION time: the plan
        # may have been computed by a shard that ran ahead of an earlier
        # move (barrier-free shard clocks), so the planned size can be
        # stale — the receiver sizes its merge from the piece itself
        align = self.owner.spec.row_align
        rows = srv.r1 - srv.r0
        move = min(move, (rows - align) // align * align)
        if move < align:
            piece = None                # no-op move, unblock the receiver
        elif recv < srv.sid:            # give away the leading edge
            piece = slice_flat(srv.state, 0, move)
            srv.state = slice_flat(srv.state, move, rows)
            srv.r0 += move
        else:                           # give away the trailing edge
            piece = slice_flat(srv.state, rows - move, rows)
            srv.state = slice_flat(srv.state, 0, rows - move)
            srv.r1 -= move
        with self._cond:
            self._pieces[wm] = piece
            if piece is not None:
                self.moves.append((wm, srv.sid, recv, move))
            self._cond.notify_all()

    def _receive(self, srv, wm, donor, move):
        with self._cond:
            while wm not in self._pieces:
                if self.owner.stop.is_set():
                    return
                self._cond.wait(timeout=0.05)
            piece = self._pieces.pop(wm)
        if piece is None:
            return                      # donor had nothing left to give
        move = int(piece["theta"].shape[-2])
        if donor < srv.sid:             # rows arrive BEFORE this range
            srv.state = merge_flat([piece, srv.state])
            srv.r0 -= move
        else:                           # rows arrive AFTER this range
            srv.state = merge_flat([srv.state, piece])
            srv.r1 += move


class _ShardServer:
    """One row-range shard: a lean single-threaded master over rows
    [r0, r1).  The serve loop mirrors ``Master.serve`` (drain -> reorder
    -> chunk to warmed power-of-two fused variants -> apply -> reply) but
    the state is a row slice and telemetry/eval flow to the owner's
    aggregators as partials instead of being recorded directly.

    Under row rebalancing (``owner.rebalancer``) the range [r0, r1) is
    MUTABLE: gradients arrive as full packed buffers and each fused
    variant slices this shard's current rows in-jit (the cache key
    carries the range, so a moved boundary simply compiles the next
    variant), and at eval watermarks the shard hands row ranges to / takes
    them from an adjacent shard through the rebalancer's rendezvous."""

    def __init__(self, sid: int, owner: "ShardedMaster", r0: int, r1: int,
                 state: dict, mailbox: Mailbox,
                 injector: FaultInjector | None):
        self.sid = sid
        self.owner = owner
        self.r0, self.r1 = r0, r1
        self.state = state              # flat dict sliced to rows [r0, r1)
        self.mailbox = mailbox
        self.injector = injector
        self.fa = owner._flat_algo
        self.stop = owner.stop
        self.total = owner.total
        self.coalesce = owner.coalesce
        self.telemetry = owner.record_telemetry
        # fused chunks never straddle an eval (or rebalance) watermark
        # (see master.run_serve_loop): all S shards snapshot / move rows
        # at the same applied counts even when their drain batches differ
        self.eval_boundary = (owner.eval_every
                              if (owner._eval_jit is not None
                                  or owner.rebalancer is not None) else 0)
        self.applied = 0
        self._step = 0
        self._fused: dict = {}
        self._view_rows_jit: dict = {}
        self._send_jit = jax.jit(self.fa.send_flat)
        if owner._gap_ex is not None:
            self._gap_partial_jit = jax.jit(self.fa.gap_partial)
            self._gap_apply_jit = jax.jit(self.fa.apply_gap_message)
            self._gap_finish_jit = jax.jit(self.fa.finish_gap_message)
        self.coalesce_counts: dict[int, int] = {}
        self.busy_s = 0.0
        self.error: BaseException | None = None
        # observability (run_serve_loop): all shards share one
        # serve_instruments bundle — its cells are per-thread, so S
        # serving threads never contend
        self.obs_cat = "shard"
        self.metrics = None

    # -- memory-tier traffic model (serve-loop counters) -----------------
    @property
    def slab_info(self):
        st = self.state
        if "v" not in st:
            return None
        n_slabs = 2 if "sent" in st else 1
        return (int(st["v"].shape[0]),
                2 * int(st["v"].shape[-2]) * n_slabs)

    # -- fused coalesced receive over this shard's rows ------------------
    def _get_fused(self, k: int, telemetry: bool):
        # under rebalancing the wire carries FULL packed gradients and
        # the slice happens here, in-jit; the key carries the current
        # range so a moved boundary compiles a fresh variant
        rows = ((self.r0, self.r1) if self.owner.rebalancer is not None
                else None)
        key = (k, telemetry, rows)
        fn = self._fused.get(key)
        if fn is not None:
            return fn
        fa = self.fa

        def fused(flat, ids, nows, g, views):
            # stacked wire format: g (and views) arrive as ONE
            # (k, rows, 128) buffer, stacked outside the jit (the single
            # master's fused_flat_program stacks inside its own); under
            # rebalancing the stack is full-height and this shard's
            # current rows slice off here
            if rows is not None:
                g = g[:, rows[0]:rows[1]]
            flat, hats, pres = fa.apply_batch(flat, ids, g, nows,
                                              telemetry=telemetry)
            out_views = tuple(hats[j] for j in range(k))
            if telemetry:
                d = pres - views
                # partial sums only: the owner adds the S shard partials
                # and takes the sqrt once per message
                return (flat, out_views, jnp.sum(d * d, axis=(1, 2)),
                        jnp.sum(g * g, axis=(1, 2)))
            return flat, out_views, None, None

        # shard state donated: in-place kernel update (see Master)
        fn = jax.jit(fused, donate_argnums=(0,))
        self._fused[key] = fn
        return fn

    def warm(self, hot_ranges: tuple = ()):
        if self.owner.rebalancer is not None:
            # rebalance wire mode: full packed gradients on the wire
            zero = jnp.zeros((self.owner.spec.rows,
                              self.state["theta"].shape[-1]), jnp.float32)
        else:
            zero = jnp.zeros_like(self.state["theta"])
        view = self.state["theta"]
        if self.owner._gap_ex is not None:
            i0 = jnp.int32(0)
            self._gap_partial_jit(self.state, i0)
            out = self._gap_apply_jit(self.state, i0, zero,
                                      jnp.float32(0.0),
                                      view if self.telemetry else None)
            st = self._gap_finish_jit(out[0], jnp.float32(0.0), out[3],
                                      out[4])
            jax.block_until_ready(st["theta"])
            return
        k = 1
        while k <= self.coalesce:
            fn = self._get_fused(k, self.telemetry)
            # stacked wire format; the fused pass donates its state
            # argument, so warm on a copy
            g = jnp.zeros((k,) + zero.shape, zero.dtype)
            out = fn(jax.tree.map(jnp.copy, self.state),
                     jnp.zeros((k,), jnp.int32),
                     jnp.zeros((k,), jnp.float32), g,
                     jnp.broadcast_to(view, (k,) + view.shape)
                     if self.telemetry else None)
            jax.block_until_ready(jax.tree.leaves(out[0])[0])
            k *= 2
        if not self.owner._sent_family:
            # shard-local hot-row view closures (see Master.warm): the
            # fan-out slices a declared (r0, r1) to this shard's range,
            # so warm exactly the sliced keys pull replies will see
            for r0, r1 in hot_ranges:
                fn = self._view_rows_fn(int(r0), int(r1))
                jax.block_until_ready(fn(self.state, jnp.int32(0)))

    def _apply_gap(self, work: list):
        """Gap-aware shard apply: the whole drained chunk, two norm
        combines per message through the streaming ``_NormExchange``
        ring (see its docstring — one blocking rendezvous per drained
        batch in the balanced case).  Messages stay strictly sequential
        (each needs the combined global norms of its predecessors), so
        the batch win is amortized drain/reply/dispatch, exactly like
        the legacy per-message kernel path."""
        telemetry = self.telemetry
        ex = self.owner._gap_ex
        for m in work:
            i = jnp.int32(m.worker_id)
            seq = self.applied
            partial = float(self._gap_partial_jit(self.state, i))
            gap2 = ex.combine(self.sid, seq, 0, partial)
            st, hat, vn2, lr, vs, d2, g2 = self._gap_apply_jit(
                self.state, i, m.grad, jnp.float32(gap2),
                m.view if telemetry else None)
            vn2_t = ex.combine(self.sid, seq, 1, float(vn2))
            self.state = self._gap_finish_jit(st, jnp.float32(vn2_t),
                                              lr, vs)
            t0 = self._step
            self._step = t0 + 1
            self.applied += 1
            if self.sid == 0 and self.applied == self.owner._steady_mark:
                self.owner.steady_t = time.perf_counter()
            if telemetry:
                m.group.add_telemetry(
                    self.sid, worker=m.worker_id, step=t0 + 1,
                    lag=t0 - m.view_step, t=self.owner._time_fn(m),
                    d2=float(d2), g2=float(g2))
            m.respond(Reply(view=hat, step=t0 + 1))
            if (self.applied % self.owner.eval_every == 0
                    or self.applied == self.total):
                self.owner._eval_contribute(self.sid, self.applied,
                                            self.state["theta"],
                                            self.owner._time_fn(m))

    def _apply(self, work: list):
        if self.owner._gap_ex is not None:
            return self._apply_gap(work)
        k = len(work)
        telemetry = self.telemetry
        fn = self._get_fused(k, telemetry)
        ids = jnp.asarray([m.worker_id for m in work], jnp.int32)
        nows = jnp.asarray([m.t_send for m in work], jnp.float32)
        grads = jnp.stack([m.grad for m in work])    # stacked wire format
        views = (jnp.stack([m.view for m in work]) if telemetry else None)
        t0 = self._step
        st, out_views, d2, g2 = fn(self.state, ids, nows, grads, views)
        if self.owner.rebalancer is not None:
            # SYNC AUDIT (survives): rebalancing steers by busy_s, but
            # JAX dispatch is async — without a sync the heavy shard's
            # compute finishes outside its timed window and busy_s
            # measures only dispatch.  Sync here (inside run_serve_loop's
            # busy_s interval) so the gauge is proportional to this
            # shard's actual row load.
            jax.block_until_ready(st["theta"])
        self.state = st
        self._step = t0 + k
        if telemetry:
            # SYNC AUDIT (survives): unlike the single master's deferred
            # spool, the S>1 partial sums must convert to floats HERE —
            # the _ReplyGroup contract flushes a telemetry row the moment
            # the last shard contributes and BEFORE the worker unblocks,
            # so deferring the host transfer would close groups without
            # their partials (a silent tele_dropped).  One transfer per
            # batch per shard, same as before.
            d2 = np.asarray(d2)
            g2 = np.asarray(g2)
        evals = []
        for j, m in enumerate(work):
            self.applied += 1
            if self.sid == 0 and self.applied == self.owner._steady_mark:
                self.owner.steady_t = time.perf_counter()
            if telemetry:
                # partials BEFORE the reply: once the worker unblocks,
                # every shard has already contributed this message's sums
                m.group.add_telemetry(
                    self.sid, worker=m.worker_id, step=t0 + j + 1,
                    lag=t0 + j - m.view_step, t=self.owner._time_fn(m),
                    d2=float(d2[j]), g2=float(g2[j]))
            m.respond(Reply(view=out_views[j], step=t0 + j + 1))
            if (self.applied % self.owner.eval_every == 0
                    or self.applied == self.total):
                evals.append((self.owner._time_fn(m), self.applied))
        # eval snapshots use the post-batch state (the single master's
        # semantics with coalescing; exact at k=1, i.e. deterministic mode)
        for t_ev, step_ev in evals:
            self.owner._eval_contribute(self.sid, step_ev,
                                        self.state["theta"], t_ev)
        # row moves happen AFTER the eval contribution, so an eval and a
        # move at the same watermark both see the pre-move ranges
        if self.owner.rebalancer is not None:
            self.owner.rebalancer.at_watermark(self)

    def _view_rows_fn(self, r0: int, r1: int):
        fn = self._view_rows_jit.get((r0, r1))
        if fn is None:
            fa = self.fa
            fn = jax.jit(lambda fl, i, a=r0, b=r1:
                         fa.view_rows(fl, i, a, b))
            self._view_rows_jit[(r0, r1)] = fn
        return fn

    def _pull_reply(self, m: GradMsg) -> int:
        if m.rows is not None and not self.owner._sent_family:
            # hot-row pull over this shard's local-row intersection
            # (possibly empty); sent-snapshot members need the full-range
            # send below (it refreshes the worker's snapshot rows)
            r0, r1 = int(m.rows[0]), int(m.rows[1])
            view = self._view_rows_fn(r0, r1)(self.state,
                                              jnp.int32(m.worker_id))
            m.respond(Reply(view=view, step=self._step, rows=(r0, r1)))
            return r1 - r0
        view, self.state = self._send_jit(self.state,
                                          jnp.int32(m.worker_id))
        m.respond(Reply(view=view, step=self._step))
        return int(view.shape[-2])

    # -- shard serve loop -------------------------------------------------
    def serve(self):
        # the shared loop (drain -> truncate -> reorder -> chunk ->
        # apply); unlike Master.serve it must NOT raise the stop flag on
        # normal completion — sibling shards may still be draining
        # (errors do stop the cluster, inside run_serve_loop)
        run_serve_loop(self)


class ShardedMaster:
    """S independent row-range shard servers over ONE flat layout.

    Drop-in for ``Master`` in the runtime: same worker-visible surface
    (``initial_view`` / ``state`` / ``master_params`` / ``applied`` /
    ``step`` / ``serve`` / ``warm`` / ``reject_pending``), but workers
    talk to it through ``frontdoor`` (a ``FanoutMailbox``) and the wire
    format is the range-ordered tuple of row slices.  Requires the flat
    kernel path (a kernel-eligible algorithm; lr schedules are fine —
    the fused pass feeds per-message lr(t)/lr(t+1) + the lazy momentum
    -correction rescale, see ``repro.kernels.flat_update``).
    """

    def __init__(self, algo: Algorithm, state: dict, *, shards: int,
                 history: History, stop: threading.Event, total_grads: int,
                 coalesce: int = 1, record_telemetry: bool = True,
                 eval_fn: Callable | None = None, eval_every: int = 100,
                 injectors: list[FaultInjector] | None = None,
                 time_fn: Callable[[GradMsg], float] | None = None,
                 mailbox_capacity: int = 0,
                 use_pallas: bool | None = None,
                 ranges: tuple | None = None,
                 rebalance: bool = False,
                 rebalance_threshold: float = 1.1):
        if shards < 1:
            raise ValueError(f"need shards >= 1, got {shards}")
        if not kernel_eligible(algo):
            raise ValueError(f"sharded master requires a kernel-eligible "
                             f"algorithm, got {algo.name!r}")
        if injectors is not None and len(injectors) != shards:
            raise ValueError("need one injector per shard")
        self.algo = algo
        self._flat_algo = FlatAlgorithm(algo, use_pallas)
        flat = self._flat_algo.adopt(state)
        self.spec = self._flat_algo.spec
        if ranges is not None:
            # caller-chosen initial ranges (a skewed placement is the
            # rebalancer's natural starting point); same invariants as
            # row_ranges: contiguous, ordered, non-empty, covering
            ranges = tuple((int(a), int(b)) for a, b in ranges)
            if (len(ranges) != shards or ranges[0][0] != 0
                    or ranges[-1][1] != self.spec.rows
                    or any(a >= b for a, b in ranges)
                    or any(ranges[s][1] != ranges[s + 1][0]
                           for s in range(shards - 1))):
                raise ValueError(f"ranges must be {shards} contiguous "
                                 f"non-empty ranges covering "
                                 f"[0, {self.spec.rows}), got {ranges}")
            self.ranges = ranges
        else:
            self.ranges = self.spec.row_ranges(shards)
        self.subs = [self.spec.subspec(r0, r1) for r0, r1 in self.ranges]
        self.rebalancer = None
        if rebalance:
            if self._flat_algo.fam.gap_aware:
                raise ValueError("row rebalancing is not supported for "
                                 "gap-aware members (the cross-shard norm"
                                 " exchange assumes fixed ranges)")
            if record_telemetry:
                raise ValueError("row rebalancing requires "
                                 "record_telemetry=False (telemetry "
                                 "views are sliced to static ranges)")
            self.rebalancer = RowRebalancer(
                self, every=max(1, eval_every),
                threshold=rebalance_threshold)
        self.num_shards = shards
        self.history = history
        self.stop = stop
        self.total = total_grads
        self.coalesce = max(1, coalesce)
        # gap-aware members exchange two global norms per message across
        # shards through the streaming ring exchange; the PR-4 coalesce=1
        # clamp is gone — drained batches apply in one _apply_gap call.
        # EXCEPT under per-shard REORDER injection: the exchange pairs
        # partials by applied count, which requires every shard to apply
        # the identical order — a reordered chunk on one shard would
        # silently cross-pair norms from different messages on ALL
        # shards.  With a reordering plan attached the shards fall back
        # to per-message drains (a 1-message chunk cannot be permuted),
        # exactly the PR-4 behavior the fault tests pin; stall/dropout
        # -only plans keep the batched exchange (order stays identical).
        self._gap_ex = None
        if self._flat_algo.fam.gap_aware:
            if injectors is not None and any(
                    inj.plan.reorder_prob > 0 for inj in injectors):
                self.coalesce = 1
            self._gap_ex = _NormExchange(shards, stop)
        self.record_telemetry = record_telemetry
        self.eval_every = max(1, eval_every)
        self._eval_jit = jax.jit(eval_fn) if eval_fn is not None else None
        self._time_fn = time_fn or (lambda m: m.t_send)
        self._inv_sqrt_p = 1.0 / math.sqrt(self.spec.n_elems)
        # stateful-send members restamp the applying worker's
        # snapshot/lane on every send, so per-update staleness == lag
        # (same bookkeeping the single master uses on its tree path)
        self._sent_family = self._flat_algo.fam.stateful_send
        self._hist_lock = threading.Lock()
        self._eval_slots: dict = {}     # step -> {"thetas": {sid: rows}, "t"}
        self._steady_mark = max(1, total_grads // 5)
        self.steady_t: float | None = None
        self.error: BaseException | None = None
        self.state_is_flat = True
        self.mailboxes = [Mailbox(mailbox_capacity) for _ in range(shards)]
        self.shards_ = [
            _ShardServer(s, self, r0, r1, slice_flat(flat, r0, r1),
                         self.mailboxes[s],
                         injectors[s] if injectors is not None else None)
            for s, (r0, r1) in enumerate(self.ranges)
        ]
        self.tele_dropped = 0
        self.frontdoor = FanoutMailbox(
            self.mailboxes,
            tele_cb=self._record_telemetry if record_telemetry else None,
            ranges=self.ranges, full_fanout=self.rebalancer is not None,
            drop_cb=self._drop_telemetry if record_telemetry else None)

    # -- worker-visible state -------------------------------------------
    @property
    def applied(self) -> int:
        """Messages applied on EVERY shard (the lagging shard's count)."""
        return min(srv.applied for srv in self.shards_)

    @property
    def step(self) -> int:
        return self.shards_[0]._step

    def _gather_flat(self) -> dict:
        return merge_flat([srv.state for srv in self.shards_])

    @property
    def state(self) -> dict:
        return unpack_state(self.algo, self._gather_flat(), self.spec)

    def master_params(self):
        return self.spec.unpack(self.spec.concat_rows(
            [srv.state["theta"] for srv in self.shards_]))

    def initial_view(self, i: int):
        """Initial pull: the range-ordered tuple of shard view slices
        (each shard refreshes worker i's sent-snapshot rows, mirroring
        the single master's send)."""
        views = []
        for srv in self.shards_:
            view, srv.state = srv._send_jit(srv.state, jnp.int32(i))
            views.append(view)
        return tuple(views), self.step

    def warm(self, hot_ranges: tuple = ()):
        for srv, (s0, s1) in zip(self.shards_, self.ranges):
            if hot_ranges and self.rebalancer is None:
                # mirror FanoutMailbox's part_rows slicing exactly, so
                # the warmed cache keys match the shard-local ranges
                # pull replies will carry at run time
                local = tuple(
                    (max(h0, s0) - s0,
                     max(min(h1, s1), max(h0, s0)) - s0)
                    for h0, h1 in hot_ranges)
            else:
                local = ()
            srv.warm(hot_ranges=local)

    # -- cross-shard aggregation (off the hot path) ----------------------
    def _record_telemetry(self, *, worker, step, lag, t, d2, g2):
        # rows append in message-COMPLETION order: with barrier-free
        # shard clocks a later message can finish on all shards before an
        # earlier one, so live-mode History rows are not step-sorted (the
        # step field carries the order; deterministic mode is serialized
        # and stays engine-ordered — tested)
        with self._hist_lock:
            self.history.record(
                time=t, step=step, worker=worker, lag=lag,
                gap=math.sqrt(d2) * self._inv_sqrt_p,
                grad_norm=math.sqrt(g2),
                staleness=float(lag) if self._sent_family
                else float("nan"))

    def _drop_telemetry(self):
        """A fan-out group finished with partials that can never flush
        (a shard rejected the message, or shard 0 never applied it) —
        account for the dropped row instead of losing it silently."""
        with self._hist_lock:
            self.tele_dropped += 1
        mx = self.shards_[0].metrics
        if mx is not None:
            mx.tele_dropped.add(1)

    def _eval_contribute(self, sid: int, step_ev: int, theta_rows, t_ev):
        if self._eval_jit is None:
            return
        # snapshot a COPY: the contributed rows may sit in the slot while
        # the shard's donated fused pass overwrites theta in place
        theta_rows = jnp.copy(theta_rows)
        ready = None
        with self._hist_lock:
            slot = self._eval_slots.setdefault(
                step_ev, {"thetas": {}, "t": None})
            slot["thetas"][sid] = theta_rows
            if sid == 0:
                slot["t"] = t_ev
            if len(slot["thetas"]) == self.num_shards:
                ready = self._eval_slots.pop(step_ev)
        if ready is None:
            return
        theta = self.spec.concat_rows(
            [ready["thetas"][s] for s in range(self.num_shards)])
        out = self._eval_jit(self.spec.unpack(theta))
        loss, metric = (out if isinstance(out, tuple)
                        else (out, float("nan")))
        with self._hist_lock:
            self.history.record_eval(time=ready["t"], step=step_ev,
                                     loss=loss, metric=metric)

    # -- lifecycle -------------------------------------------------------
    def serve(self):
        """Run all S shard servers; returns when every shard has applied
        ``total`` gradients (or the cluster stops)."""
        threads = [
            threading.Thread(target=srv.serve, name=f"ps-shard-{srv.sid}",
                             daemon=True)
            for srv in self.shards_
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        errs = [srv.error for srv in self.shards_ if srv.error is not None]
        if errs:
            self.error = errs[0]
        self.stop.set()

    def reject_pending(self):
        """Post-shutdown: unblock any worker still waiting on a reply."""
        for mb in self.mailboxes:
            for m in mb.drain_nowait():
                m.respond(None)

    # -- aggregate stats -------------------------------------------------
    @property
    def busy_s(self) -> float:
        """Busy time of the busiest shard — the shards run concurrently,
        so the critical path (not the sum) is the master-side cost."""
        return max(srv.busy_s for srv in self.shards_)

    @property
    def coalesce_counts(self) -> dict[int, int]:
        out: dict[int, int] = {}
        for srv in self.shards_:
            for k, c in srv.coalesce_counts.items():
                out[k] = out.get(k, 0) + c
        return out

    @property
    def shard_applied(self) -> list[int]:
        return [srv.applied for srv in self.shards_]

    @property
    def current_ranges(self) -> tuple:
        """Live row ranges (sid order == row order, moves included)."""
        return tuple((srv.r0, srv.r1) for srv in self.shards_)

    @property
    def rebalance_moves(self) -> list:
        """(watermark, donor, receiver, rows) log of executed moves."""
        return ([] if self.rebalancer is None
                else list(self.rebalancer.moves))
