"""The parameter-server master: drains the mailbox, applies the algorithm.

The master is the paper's bottleneck above ~20 workers (App. C.1); the
attack here is **coalesced receive**: drain up to k queued messages and
apply them in ONE fused jit dispatch.  The fused pass preserves the
engine's exact semantics — for each message in order it runs
``receive(state, i, grad, now)`` then ``send(state, i)`` (so every worker
still gets the view it would have gotten from per-message processing) —
but pays one trace/dispatch and one host-device round trip for the whole
batch instead of k of them.

On top of coalescing sit two kernel paths:

* **flat** (the default whenever ``use_kernel``): the whole flat family
  — per-worker momentum (dana-zero, multi-asgd, dana-slim, nag-asgd,
  dana-nadam, nadam-asgd), the sent-snapshot members (dc-asgd, dana-dc,
  ga-asgd), the momentum-free/shared-look-ahead members (asgd, lwp) and
  the rate-weighted extension (dana-hetero) — runs on flat (R, 128)
  state packed ONCE at init; ``repro.kernels.flat_update`` applies all
  k drained messages in a single batched kernel (Pallas on TPU,
  bit-identical jnp reference elsewhere; gap-aware lowers to a
  two-phase Pallas grid on TPU with the jnp reference as the
  cross-backend oracle).  Message timestamps ride in as per-message
  ``nows`` so dana-hetero's rate lane advances exactly like the tree
  path's ``now`` argument.  Moving lr schedules are fed in as
  per-message lr(t)/lr(t+1) scalars with the lazy momentum-correction
  rescale, so the flat pass matches the algorithm path's receive->send
  bit-for-bit for the elementwise family, schedules included (tested).
  Look-ahead sends (pull replies, initial views) run the weighted-slab
  reduction kernel (``flat_update/send.py``).  The fused pass donates
  the flat state (``input_output_aliases`` in the kernel), halving the
  master-state traffic.  No per-call, per-leaf padding; pytrees only at
  the edges (incoming grads, outgoing views).
* **legacy tree kernel** (explicit ``flat=False``, DANA-Zero only): PR
  1's per-message ``dana_update`` routing — k sequential kernel rounds
  inside the fused jit, re-padding every leaf per call.  Kept ONLY as
  the benchmark cross-check baseline for the batched path; it still
  uses lr(t) for the look-ahead where the algorithm's send would use
  lr(t+1).

When the fused batch would cross an eval boundary, the serve loop
splits it there, so evals always observe the state at exactly a
multiple of ``eval_every`` applied messages — the same watermark on
every shard of a sharded master (cross-shard snapshot consistency).

When one master still bounds throughput, ``repro.cluster.sharded``
splits the SAME flat buffers into S row-range shard servers whose serve
loops mirror this one (``ClusterConfig(shards=S)``).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.algorithms import Algorithm, DanaZero
from ..core.metrics import History
from ..core.types import (tree_gap, tree_index, tree_l2, tree_scale,
                          tree_set_index)
from ..kernels.dana_update import dana_master_update
from ..kernels.flat_update import (FlatAlgorithm, family_spec_for,
                                   kernel_eligible)
from ..obs import trace
from .faults import FaultInjector
from .mailbox import GradMsg, Mailbox, Reply


def run_serve_loop(server):
    """The parameter-server drain loop, shared by the single ``Master``
    and each sharded ``_ShardServer`` (identical hot-path semantics, one
    implementation to fix).

    Per round: drain up to ``coalesce`` messages -> truncate gradient
    work to the remaining room (end-of-run overflow is rejected in
    ARRIVAL order, so under sharding every shard rejects the same
    messages) -> apply fault reordering to the accepted work -> chunk to
    the warmed power-of-two fused variants -> reply to pulls -> reject
    overflow.  ``server`` provides mailbox/stop/total/applied/coalesce/
    injector/eval_boundary/slab_info plus ``_apply(chunk)`` and
    ``_pull_reply(msg)`` (which returns the number of view rows served,
    0 when unknown); errors land on ``server.error`` and raise the stop
    flag.  Observability rides the existing timing: ``server.metrics``
    (a ``serve_instruments`` bundle or None) gets the drained-batch-size
    histogram, pull/overflow counters and the memory-tier traffic
    counters (``slab_info = (n_slab_workers, rows_per_sender)`` on flat
    servers, None on the tree path).

    ``server.busy_s`` accumulates the serve thread's host time inside
    ``_apply``: id/time transfers (and the sharded servers' eager
    stack), the flat master's ``RUN_AHEAD`` wait, the dispatch of the
    receive and the replies.  The receive itself runs on the device
    asynchronously, so this is host time, not device time.  When
    tracing is enabled each receive is a ``<obs_cat>.apply`` span
    (``master.apply`` or ``shard.apply``) with its size ``k``, its first
    apply ``step``, the first gradient's ``(worker, seq)`` and, where
    the server counts them, ``in_flight``: the earlier receives the
    device had not finished when this one was dispatched, and
    ``stacked``: the gradients the receive program concatenated (0 when
    it read its one gradient in place).  Pull replies are
    ``<obs_cat>.pull`` spans.

    Chunks additionally never straddle an eval boundary
    (``server.eval_boundary``, 0 when no eval is configured): evals run
    on the post-chunk state, so aligning chunk ends with multiples of
    ``eval_every`` makes every eval observe the state at EXACTLY its
    applied-count watermark — on a sharded master, every shard snapshots
    at the same watermark even when their drain batches differ
    (cross-shard eval snapshot consistency in live modes).
    """
    msgs: list[GradMsg] = []
    try:
        while server.applied < server.total and not server.stop.is_set():
            msgs = server.mailbox.drain(server.coalesce, server.stop,
                                        pow2=server.coalesce > 1)
            if not msgs:
                continue
            work = [m for m in msgs if m.grad is not None]
            pulls = [m for m in msgs if m.grad is None]
            room = server.total - server.applied
            overflow, work = work[room:], work[:room]
            if server.injector is not None:
                work = server.injector.reorder(work)
            mx = server.metrics
            while work:
                # pull filtering / end-of-run truncation can leave a
                # non-power-of-two batch; chunk it back to the warmed
                # fused variants so no compile lands mid-run (and never
                # across an eval watermark, see docstring)
                lim = min(len(work), server.coalesce)
                bnd = server.eval_boundary
                if bnd:
                    lim = min(lim, bnd - server.applied % bnd)
                k = 1 << (lim.bit_length() - 1)
                chunk, work = work[:k], work[k:]
                server.coalesce_counts[k] = \
                    server.coalesce_counts.get(k, 0) + 1
                tr = trace.enabled
                if tr:
                    trace.begin(server.obs_cat + ".apply", server.obs_cat,
                                k=k, step=server.applied + 1,
                                worker=chunk[0].worker_id, seq=chunk[0].seq)
                t_in = time.perf_counter()
                server._apply(chunk)
                dt = time.perf_counter() - t_in
                server.busy_s += dt
                if mx is not None:
                    mx.drain_k.observe(k)
                    info = server.slab_info
                    if info is not None:
                        # memory-tier traffic: the prefetch lowering
                        # streams 2 slab rows (read+write) per UNIQUE
                        # sender per slab; the full-slab kernel streams
                        # them for every worker.  Recording both makes
                        # the 2N->2u claim visible in exported series.
                        n_slab, rows2 = info
                        u = min(len({m.worker_id for m in chunk}), n_slab)
                        mx.slab_rows_streamed.add(u * rows2)
                        mx.slab_rows_total.add(n_slab * rows2)
                if tr:
                    trace.end(**{a: v for a in ("in_flight", "stacked")
                                 if (v := getattr(server, a, None))
                                 is not None})
            if pulls and mx is not None:
                mx.pulls.add(len(pulls))
            for m in pulls:
                tr = trace.enabled
                if tr:
                    trace.begin(server.obs_cat + ".pull", server.obs_cat,
                                worker=m.worker_id)
                served_rows = server._pull_reply(m)
                if mx is not None and served_rows:
                    mx.pull_rows.add(served_rows)
                if tr:
                    trace.end()
            if overflow and mx is not None:
                mx.overflow.add(len(overflow))
            for m in overflow:
                m.respond(None)
            msgs = []
    except BaseException as e:  # noqa: BLE001 - reported by run_cluster
        server.error = e
        server.stop.set()
    finally:
        # a mid-batch failure leaves drained messages unanswered;
        # release their workers instead of letting them hit rpc_timeout
        for m in msgs:
            if not m._event.is_set():
                m.respond(None)


# Unfinished receives the master lets the device queue hold when it
# dispatches another.  Each holds a gradient and a reply view, two
# state-sized buffers, and where the device bounds the run a deeper
# queue adds memory and no throughput.  The runtime itself held the
# master at two to three while an eager stack program preceded each
# receive; without that program it lets about five queue up.
RUN_AHEAD = 2


def fused_flat_program(fa, k: int, telemetry: bool):
    """The master's fused receive for a k-message drain of
    ``FlatAlgorithm`` ``fa``: ``jit(flat, ids, nows, g_flat, views) ->
    (flat, views, gaps, gnorms[, staleness], done)``, state donated.  ONE
    batched flat kernel for the whole drain, under the name scope
    ``receive``.  ``done`` is a scalar read from the updated state: it
    holds no large buffer and is never donated, so ``done.is_ready()``
    tells, without a sync, whether the device has finished this receive.

    Everything on the wire is already flat, and the batch arrives
    UNSTACKED: ``g_flat`` (and ``views`` under telemetry) is a tuple of
    k (R, 128) arrays, the drained messages' own buffers.  The program
    forms the kernel's (k, R, 128) operand itself: at k = 1 that adds a
    unit leading axis, a bitcast of the same bytes, so the kernel reads
    the gradient in place; at k > 1 it is one concatenation inside this
    program, with no dispatch of its own.  The returned views are raw
    (R, 128) hat rows — the master thread does no pytree work at all.
    """
    inv_sqrt_p = 1.0 / float(np.sqrt(fa.spec.n_elems))

    def receive(flat, ids, nows, g_flat, views):
        # per-message sent-snapshot staleness comes from the scalar
        # lane, read BEFORE apply_batch consumes the donated state
        # (None for snapshot-free members)
        stals = (fa.batch_staleness(flat, ids, k) if telemetry
                 else None)
        # k == 1: a bitcast, the kernel reads the gradient in place.
        # The barrier keeps the stacked operand a buffer of its own, as
        # an eagerly stacked one is: without it XLA's CPU backend fuses
        # the stack into the jnp reference's update and rounds it apart
        # from the sharded and process servers' programs.  The Pallas
        # kernel reads its operand whole either way.
        g_flat = jax.lax.optimization_barrier(jnp.stack(g_flat))
        flat, hats, pres = fa.apply_batch(flat, ids, g_flat, nows,
                                          telemetry=telemetry)
        out_views = tuple(hats[j] for j in range(k))
        done = flat["theta"][0, 0]
        if telemetry:
            d = pres - jnp.stack(views)  # zero in the padding region
            gaps = jnp.sqrt(jnp.sum(d * d, axis=(1, 2))) * inv_sqrt_p
            gnorms = jnp.sqrt(jnp.sum(g_flat * g_flat, axis=(1, 2)))
            return flat, out_views, gaps, gnorms, stals, done
        return flat, out_views, None, None, done

    def fused(flat, ids, nows, g_flat, views):
        with jax.named_scope("receive"):
            return receive(flat, ids, nows, g_flat, views)

    # the flat state is donated: the batched kernel aliases its state
    # inputs to its outputs (input_output_aliases), so the update
    # runs in place — callers rebind to the returned state
    return jax.jit(fused, donate_argnums=(0,))


class Master:
    def __init__(self, algo: Algorithm, state: dict, *,
                 mailbox: Mailbox, history: History, stop: threading.Event,
                 total_grads: int, coalesce: int = 1,
                 use_kernel: bool = False, flat: bool | None = None,
                 record_telemetry: bool = True,
                 eval_fn: Callable | None = None, eval_every: int = 100,
                 injector: FaultInjector | None = None,
                 time_fn: Callable[[GradMsg], float] | None = None,
                 pipeline_depth: int = 0):
        self.algo = algo
        self._tree_state: dict | None = state
        self._flat_algo: FlatAlgorithm | None = None
        self._flat_state: dict | None = None
        if use_kernel:
            if flat is None:
                # flat is the universal kernel substrate (schedules
                # included); the legacy per-message dana_update routing
                # survives only as an explicit flat=False baseline
                flat = True
            if flat:
                if not kernel_eligible(algo):
                    raise ValueError(f"use_kernel=True but {algo.name!r} "
                                     f"is not kernel-eligible")
                self._flat_algo = FlatAlgorithm(algo)
                self._flat_state = self._flat_algo.adopt(state)
                self._tree_state = None
            elif type(algo) is not DanaZero:
                raise ValueError(
                    f"the legacy (flat=False) kernel path implements "
                    f"exactly DANA-Zero, got {algo.name!r}")
        self.state_is_flat = self._flat_algo is not None
        self.mailbox = mailbox
        self.history = history
        self.stop = stop
        self.total = total_grads
        self.coalesce = max(1, coalesce)
        self.use_kernel = use_kernel
        self.record_telemetry = record_telemetry
        self.eval_every = max(1, eval_every)
        self.injector = injector
        self.error: BaseException | None = None
        self.applied = 0                   # gradient messages applied
        self._step = 0                     # master update counter (host copy)
        self._fused: dict = {}             # (k, telemetry) -> jitted pass
        self._send_jit = jax.jit(algo.send)
        if self.state_is_flat:
            # flat mode keeps the WIRE format flat too: workers receive
            # (R, 128) views and push (R, 128) gradients (runtime wraps
            # their grad_fn with unpack/pack), so the master thread never
            # touches a pytree on the hot path.  send_flat returns the
            # (possibly) updated state: the sent-snapshot family
            # refreshes worker i's slab row on every pull.
            self._flat_send_jit = jax.jit(self._flat_algo.send_flat)
        self._eval_jit = jax.jit(eval_fn) if eval_fn is not None else None
        # fused chunks never straddle a multiple of this applied count
        # (0 = unconstrained): evals observe exact watermark states
        self.eval_boundary = self.eval_every if eval_fn is not None else 0
        # time source for History rows (virtual in deterministic/paced
        # modes, wall-clock seconds in free mode)
        self._time_fn = time_fn or (lambda m: m.t_send)
        self.coalesce_counts: dict[int, int] = {}   # drained-k histogram
        # observability: trace span category + serve-side instrument
        # bundle (attached by run_cluster when a registry is passed)
        self.obs_cat = "master"
        self.metrics = None
        # stateful-send members (dc-asgd, dana-dc, ga-asgd, sa-asgd)
        # restamp a worker's snapshot/lane on every send, so per-update
        # staleness == lag — and pure-view fast paths (warm hot-range
        # closures, hot-row pulls) must fall back to the full send;
        # stateless-send members record NaN (no stamp to age)
        fam = family_spec_for(algo)
        self._sent_family = fam is not None and fam.stateful_send
        # worker pull-ahead depth (staleness accounting only — the
        # workers implement the pipelining; see _flush_telemetry)
        self._pipeline_depth = max(0, int(pipeline_depth))
        # deferred telemetry: per-batch device arrays + host metadata,
        # flushed to History at eval watermarks / cap / end of run
        self._tele_spool: list = []
        self._tele_cap = 64
        # memory-tier traffic model for the serve-loop counters: slab
        # worker count + rows one sender streams (2 r/w streams per slab)
        self.slab_info = None
        if self.state_is_flat and "v" in self._flat_state:
            n_slab = int(self._flat_state["v"].shape[0])
            n_slabs = 2 if "sent" in self._flat_state else 1
            rows = int(self._flat_state["v"].shape[-2])
            self.slab_info = (n_slab, 2 * rows * n_slabs)
        # hot-row pulls: one jitted row-sliced view closure per distinct
        # (static) requested range — see FlatAlgorithm.view_rows
        self._view_rows_jit: dict = {}
        # steady-state marker: wall time when 20% of the grads have been
        # applied (compile + ramp-up excluded from steady throughput)
        self._steady_mark = max(1, total_grads // 5)
        self.steady_t: float | None = None
        # the master thread's host time in _apply (drain waits excluded):
        # transfers, the RUN_AHEAD wait, the receive's dispatch and the
        # replies.  The receive runs asynchronously, so this is host
        # time, not device time
        self.busy_s = 0.0
        # flat path: the ``done`` scalars of the last receives, newest
        # last (the RUN_AHEAD bound waits on the oldest); traced runs
        # also record how many were unfinished at the latest dispatch
        # and how many gradients that receive's program concatenated
        self._done: collections.deque = collections.deque(
            maxlen=RUN_AHEAD + 1)
        self.in_flight: int | None = None
        self.stacked: int | None = None

    # -- worker-visible state -------------------------------------------
    @property
    def step(self) -> int:
        return self._step

    @property
    def state(self) -> dict:
        """The algorithm's pytree state (unpacked on demand in flat mode)."""
        if self.state_is_flat:
            return self._flat_algo.tree_state(self._flat_state)
        return self._tree_state

    def master_params(self):
        if self.state_is_flat:
            return self._flat_algo.master_params(self._flat_state)
        return self.algo.master_params(self._tree_state)

    def initial_view(self, i: int):
        """Initial parameter pull for worker i (call in order 0..n-1 from
        ONE thread before workers start — mirrors the engine's warm-up)."""
        if self.state_is_flat:
            view, self._flat_state = self._flat_send_jit(self._flat_state,
                                                         jnp.int32(i))
            return view, self._step
        view, self._tree_state = self._send_jit(self._tree_state,
                                                jnp.int32(i))
        return view, self._step

    def warm(self, hot_ranges: tuple = ()):
        """Pre-compile every fused-receive variant the drain policy can
        produce (powers of two up to the coalesce window) so no compile
        lands mid-run.  Compile only: the variants are lowered against
        the live state and abstract batches, so warming runs nothing and
        holds no second copy of the state on the device.

        ``hot_ranges`` — the distinct ``ClusterConfig.hot_rows`` (r0, r1)
        ranges workers declared: their row-sliced view closures
        (``_view_rows_jit``) are compiled here too, so the first hot-row
        pull never traces mid-run (snapshot-free families only — the
        sent family always serves full-range pulls)."""
        def abstract(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        if self.state_is_flat:
            theta = self._flat_state["theta"]
        else:
            params = jax.tree.map(abstract, self.master_params())
        k = 1
        while k <= self.coalesce:
            ids = jax.ShapeDtypeStruct((k,), jnp.int32)
            nows = jax.ShapeDtypeStruct((k,), jnp.float32)
            # the wire format: the k drained gradients as they arrive
            one = abstract(theta) if self.state_is_flat else params
            grads = tuple(one for _ in range(k))
            views = grads if self.record_telemetry else None
            fn, st = self._fused_for(k, self.record_telemetry)
            fn.lower(st, ids, nows, grads, views).compile()
            k *= 2
        if self.state_is_flat and not self._sent_family:
            for r0, r1 in hot_ranges:
                fn = self._view_rows_fn(int(r0), int(r1))
                jax.block_until_ready(fn(self._flat_state, jnp.int32(0)))

    # -- fused coalesced receive ----------------------------------------
    def _fused_for(self, k: int, telemetry: bool):
        if self.state_is_flat:
            return self._get_fused_flat(k, telemetry), self._flat_state
        return self._get_fused(k, telemetry), self._tree_state

    def _get_fused_flat(self, k: int, telemetry: bool):
        key = ("flat", k, telemetry)
        fn = self._fused.get(key)
        if fn is None:
            fn = self._fused[key] = fused_flat_program(self._flat_algo, k,
                                                       telemetry)
        return fn

    def _get_fused(self, k: int, telemetry: bool):
        key = (k, telemetry)
        fn = self._fused.get(key)
        if fn is not None:
            return fn
        algo = self.algo
        kernel = self.use_kernel and not self.state_is_flat

        def _one(state, i, grad, now):
            if not kernel:
                return algo.receive_send(state, i, grad, now)
            # legacy per-message Pallas/ref dana_update round (PR 1):
            # true-scale values in, stored scale (v_true / vscale) out
            lr, vscale = algo._lr_and_vscale(state)
            vi_old = tree_index(state["v"], i)
            theta, vi, v0n, theta_hat = dana_master_update(
                state["theta0"], tree_scale(vscale, vi_old),
                tree_scale(vscale, state["v0"]), grad, lr,
                algo.hp.momentum)
            inv = 1.0 / vscale
            state = dict(state)
            state.update(theta0=theta,
                         v=tree_set_index(state["v"], i,
                                          tree_scale(inv, vi)),
                         v0=tree_scale(inv, v0n), vscale=vscale,
                         t=state["t"] + 1, lr_prev=lr)
            return state, theta_hat

        def fused(state, ids, nows, grads, views):
            out_views, gaps, gnorms = [], [], []
            for j in range(k):
                if telemetry:
                    gaps.append(tree_gap(algo.master_params(state),
                                         views[j]))
                    gnorms.append(tree_l2(grads[j]))
                state, view = _one(state, ids[j], grads[j], nows[j])
                out_views.append(view)
            if telemetry:
                # staleness slot: None on the tree path — the host
                # computes it from view_step in _apply (== lag for the
                # sent-snapshot family, NaN otherwise)
                return state, tuple(out_views), jnp.stack(gaps), \
                    jnp.stack(gnorms), None
            return state, tuple(out_views), None, None

        fn = jax.jit(fused)
        self._fused[key] = fn
        return fn

    def _apply(self, work: list[GradMsg]):
        k = len(work)
        telemetry = self.record_telemetry
        fn, st = self._fused_for(k, telemetry)
        ids = jnp.asarray([m.worker_id for m in work], jnp.int32)
        nows = jnp.asarray([m.t_send for m in work], jnp.float32)
        tr = trace.enabled
        # the drained gradients go to the receive as they are: the flat
        # program stacks them itself (in place at k == 1)
        grads = tuple(m.grad for m in work)
        views = tuple(m.view for m in work) if telemetry else None
        if self.state_is_flat:
            if len(self._done) > RUN_AHEAD:
                # at most RUN_AHEAD earlier receives stay unfinished
                self._done[0].block_until_ready()
            if tr:
                self.in_flight = sum(not d.is_ready() for d in self._done)
                self.stacked = 0 if k == 1 else k
        t0 = self._step
        out = fn(st, ids, nows, grads, views)
        if self.state_is_flat:
            *out, done = out
            self._done.append(done)
        if telemetry:
            st, out_views, gaps, gnorms, stals = out
        else:
            st, out_views, _, _ = out
            gaps = gnorms = stals = None
        if self.state_is_flat:
            self._flat_state = st
        else:
            self._tree_state = st
        self._step = t0 + k
        if telemetry:
            # sync-free serve loop: keep gaps/gnorms/stals as DEVICE
            # arrays and spool the per-message metadata — the host never
            # blocks on this batch's results, so batch B+1 dispatches
            # while the device still runs batch B.  The spool flushes to
            # History at eval watermarks / the spool cap / end of run,
            # replaying record() calls in identical order (bit-identical
            # series; tested).
            metas = [(self._time_fn(m), m.worker_id, m.view_step)
                     for m in work]
            self._tele_spool.append((t0, metas, gaps, gnorms, stals))
        evals = []
        for j, m in enumerate(work):
            self.applied += 1
            if self.applied == self._steady_mark:
                self.steady_t = time.perf_counter()
            m.respond(Reply(view=out_views[j], step=t0 + j + 1))
            if (self.applied % self.eval_every == 0
                    or self.applied == self.total):
                evals.append((self._time_fn(m), t0 + j + 1))
        if telemetry and (evals or len(self._tele_spool)
                          >= self._tele_cap):
            self._flush_telemetry()
        # eval uses the post-batch state; with coalescing k=1 (always true
        # in deterministic mode) this is exactly the engine's eval point.
        for t_ev, step_ev in evals:
            self._eval(t_ev, step_ev)

    def _flush_telemetry(self):
        """Drain the deferred telemetry spool into ``History`` — the only
        point where the master thread syncs with the device for
        telemetry (one host transfer per spooled batch, all off the
        per-batch hot path)."""
        spool, self._tele_spool = self._tele_spool, []
        for t0, metas, gaps, gnorms, stals in spool:
            gaps = np.asarray(gaps)
            gnorms = np.asarray(gnorms)
            if stals is not None:
                stals = np.asarray(stals)
            for j, (t_m, wid, vstep) in enumerate(metas):
                if self._pipeline_depth and self._sent_family:
                    # pull-ahead: the pushed grad was computed against an
                    # OLDER reply than the one that last restamped this
                    # worker's snapshot lane, so the lane undercounts by
                    # the pipeline depth — the message lag is the true
                    # snapshot age
                    stal = float(t0 + j - vstep)
                elif stals is not None:          # flat path: lane-based
                    stal = float(stals[j])
                elif self._sent_family:          # tree path: == lag
                    stal = float(t0 + j - vstep)
                else:
                    stal = float("nan")
                self.history.record(
                    time=t_m, step=t0 + j + 1, worker=wid,
                    lag=t0 + j - vstep, gap=float(gaps[j]),
                    grad_norm=float(gnorms[j]), staleness=stal)

    def _eval(self, t, step):
        if self._eval_jit is None:
            return
        out = self._eval_jit(self.master_params())
        loss, metric = (out if isinstance(out, tuple)
                        else (out, float("nan")))
        self.history.record_eval(time=t, step=step, loss=loss, metric=metric)

    def _view_rows_fn(self, r0: int, r1: int):
        """The jitted row-sliced view closure for one static hot-row
        range — cached per range, pre-compiled by ``warm`` for declared
        ranges so no trace lands mid-run."""
        fn = self._view_rows_jit.get((r0, r1))
        if fn is None:
            fa = self._flat_algo
            fn = jax.jit(lambda fl, i, a=r0, b=r1:
                         fa.view_rows(fl, i, a, b))
            self._view_rows_jit[(r0, r1)] = fn
        return fn

    def _pull_reply(self, m: GradMsg) -> int:
        if self.state_is_flat:
            if m.rows is not None and not self._sent_family:
                # hot-row pull: serve the view over only the declared
                # rows (row-local reduction, bit-equal to the full
                # view's slice).  Sent-snapshot members never take this
                # branch — their send must refresh the worker's full
                # snapshot slab row, so they fall through to the
                # full-range send below (Reply.rows stays None and the
                # worker replaces its whole view).
                r0, r1 = int(m.rows[0]), int(m.rows[1])
                view = self._view_rows_fn(r0, r1)(self._flat_state,
                                                  jnp.int32(m.worker_id))
                m.respond(Reply(view=view, step=self._step,
                                rows=(r0, r1)))
                return r1 - r0
            view, self._flat_state = self._flat_send_jit(
                self._flat_state, jnp.int32(m.worker_id))
            m.respond(Reply(view=view, step=self._step))
            return int(view.shape[-2])
        view, self._tree_state = self._send_jit(self._tree_state,
                                                jnp.int32(m.worker_id))
        m.respond(Reply(view=view, step=self._step))
        return 0

    # -- main loop -------------------------------------------------------
    def serve(self):
        try:
            run_serve_loop(self)
        finally:
            try:
                if self.record_telemetry:
                    self._flush_telemetry()
            except BaseException as e:  # noqa: BLE001 - surfaced below
                if self.error is None:
                    self.error = e
            finally:
                self.stop.set()     # run over (or failed): cluster done

    def reject_pending(self):
        """Post-shutdown: unblock any worker still waiting on a reply."""
        for m in self.mailbox.drain_nowait():
            m.respond(None)
