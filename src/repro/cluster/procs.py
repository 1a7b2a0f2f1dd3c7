"""Process-backed shard cluster: shard servers and workers as OS processes.

The threaded backend (``runtime.py``) keeps every shard server and every
worker inside one CPython process, so S serving threads contend on one
GIL / one JAX dispatch lock — the capacity sweep's S=8 cliff (ROADMAP
item 1).  This module runs the SAME protocol across process boundaries:

* ``ShmMailbox`` / ``ShmFanout`` — the ``Mailbox`` / ``FanoutMailbox``
  pair over one preallocated ``multiprocessing.shared_memory`` block.
  The flat wire format is already process-friendly: a message is a
  contiguous ``(rows_s, 128)`` f32 slice per shard, so each shard ring
  preallocates ``cap`` slots of grad / telemetry-view / reply payload
  plus an 8-cell int64 meta header per slot.  Slot hand-off is
  futex-style generation stamping (value first, stamp second; bounded
  spin then a sleeping wait): ``req_gen`` publishes a request,
  ``rep_gen`` a reply, ``con_gen`` the worker's final consumption that
  frees the slot for reuse.  One GLOBAL reserve counter (under one
  ``mp.Lock``) orders every message across all shard rings — the atomic
  fan-out that keeps each shard's arrival order identical, exactly the
  ``FanoutMailbox`` contract.
* ``Mailbox.depth`` gauge contract carried over: depth is
  ``reserve_counter - ring_read_index``, two lock-free int64 loads, so
  the PR-6 ``SnapshotPublisher`` samples per-shard depth / ``busy_s``
  from the parent with zero child cooperation.
* ``run_cluster_procs`` replays the threaded lifecycle: warm-up sends
  in worker order on the parent, per-shard warm/serve/reject_pending in
  server children, child exceptions + exit codes surfaced through the
  same ``cluster run failed in <name>`` path, telemetry / eval / drain-k
  instruments shipped back over pipes and merged post-hoc so History
  rows and the metrics registry look exactly like a threaded run.

Spawn, not fork: JAX is initialized in the parent, and forking a
process with live XLA threads deadlocks.  Children therefore re-import
and re-jit (warm-up happens before workers start, so compile time never
lands mid-run) — which is also why ``grad_fn`` / ``next_batch`` must be
picklable for this backend (closures are rejected with a pointed
error; see ``repro.models.toy.ClassifierGradFn`` and the
real-model ``repro.models.api.ModelGradFn``).

Scope (enforced by ``run_cluster``): live modes only, kernel-eligible
algorithms on the flat path, no dropout / hot-row pulls / rebalancing /
custom shard ranges; gap-aware only at shards=1 (its cross-shard norm
exchange is a threads-only hot path).  ``pin_schedule=True`` adds a
round-robin turn gate on both backends so the two produce the identical
message schedule — the bit-exact equivalence harness.
"""
from __future__ import annotations

import math
import pickle
import sys
import time
import traceback
from collections import deque

import numpy as np

LANES = 128

# control-block int64 cells
C_STOP, C_SHUTDOWN, C_RSV, C_TURN, C_CTL = 0, 1, 2, 3, 4
# per-slot meta int64 cells
M_REQ, M_REP, M_CON, M_WID, M_VSTEP, M_RSTEP, M_ROK, M_N = range(8)
# control-block f64 cells
F_T0, F_STEADY, F_CTL = 0, 1, 2

_SPINS = 400           # GIL/CPU-yield spins before the sleeping fallback
_SLEEP = 5e-5
_STOP_GRACE = 2.0      # post-stop reply grace before a waiter gives up


class ShmLayout:
    """Picklable descriptor of the shared block: offsets + ring geometry.

    One block holds the control cells, then per shard a ring of ``cap``
    slots (meta int64[8], t_send f64, grad / view / rep f32 payloads of
    that shard's row count).  Every array is 8-byte aligned by
    construction (row payloads are multiples of 512 bytes)."""

    def __init__(self, ranges, num_workers: int, cap: int,
                 telemetry: bool):
        self.ranges = tuple((int(a), int(b)) for a, b in ranges)
        self.shards = len(self.ranges)
        self.num_workers = int(num_workers)
        self.cap = int(cap)
        self.telemetry = bool(telemetry)
        S, n = self.shards, self.num_workers
        off = 0
        self.o_ctl_i = off
        self.n_ctl_i = C_CTL + 2 * S          # + per-shard ridx, applied
        off += 8 * self.n_ctl_i
        self.o_ctl_f = off
        self.n_ctl_f = F_CTL + S              # + per-shard busy_s
        off += 8 * self.n_ctl_f
        self.o_ring = []
        for r0, r1 in self.ranges:
            rows = r1 - r0
            o = {}
            o["meta"] = off
            off += 8 * M_N * cap
            o["tsend"] = off
            off += 8 * cap
            o["grad"] = off
            off += 4 * cap * rows * LANES
            if telemetry:
                o["view"] = off
                off += 4 * cap * rows * LANES
            o["rep"] = off
            off += 4 * cap * rows * LANES
            o["rows"] = rows
            self.o_ring.append(o)
        self.total = off

    # -- numpy views over an attached buffer -----------------------------
    def ctl_i(self, buf):
        return np.ndarray((self.n_ctl_i,), np.int64, buf, self.o_ctl_i)

    def ctl_f(self, buf):
        return np.ndarray((self.n_ctl_f,), np.float64, buf, self.o_ctl_f)

    def ring(self, buf, sid: int) -> dict:
        o, cap = self.o_ring[sid], self.cap
        rows = o["rows"]
        out = {
            "meta": np.ndarray((cap, M_N), np.int64, buf, o["meta"]),
            "tsend": np.ndarray((cap,), np.float64, buf, o["tsend"]),
            "grad": np.ndarray((cap, rows, LANES), np.float32, buf,
                               o["grad"]),
            "rep": np.ndarray((cap, rows, LANES), np.float32, buf,
                              o["rep"]),
        }
        if self.telemetry:
            out["view"] = np.ndarray((cap, rows, LANES), np.float32,
                                     buf, o["view"])
        return out


def _pause(spins: int) -> int:
    """One step of a bounded-spin-then-sleep wait; returns spins + 1."""
    if spins < _SPINS:
        time.sleep(0)
    else:
        time.sleep(_SLEEP)
    return spins + 1


class _ShmStop:
    """``threading.Event`` facade over the shared stop cell."""

    __slots__ = ("_ctl",)

    def __init__(self, ctl_i):
        self._ctl = ctl_i

    def is_set(self) -> bool:
        return bool(self._ctl[C_STOP])

    def set(self):
        self._ctl[C_STOP] = 1

    def wait(self, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.is_set():
                return True
            time.sleep(min(2e-3, timeout))
        return self.is_set()


class _ShmMsg:
    """Server-side view of one ring slot, duck-typing ``GradMsg`` for
    ``run_serve_loop`` (grad/view are zero-copy numpy views into the
    block; ``respond`` writes the reply payload then publishes the
    ``rep_gen`` stamp).  ``idx`` is the global reservation index — the
    cross-shard message identity the parent uses to re-pair telemetry
    partials after the run."""

    __slots__ = ("idx", "worker_id", "grad", "view", "view_step",
                 "t_send", "rows", "_ring", "_slot", "_gen")

    def __init__(self, idx, ring, slot, gen, telemetry):
        meta = ring["meta"][slot]
        self.idx = idx
        self.worker_id = int(meta[M_WID])
        self.view_step = int(meta[M_VSTEP])
        self.t_send = float(ring["tsend"][slot])
        self.grad = ring["grad"][slot]
        self.view = ring["view"][slot] if telemetry else None
        self.rows = None
        self._ring = ring
        self._slot = slot
        self._gen = gen

    def respond(self, reply):
        ring, slot = self._ring, self._slot
        meta = ring["meta"][slot]
        if reply is None:
            meta[M_ROK] = 0
        else:
            np.copyto(ring["rep"][slot], np.asarray(reply.view))
            meta[M_RSTEP] = int(reply.step)
            meta[M_ROK] = 1
        meta[M_REP] = self._gen        # publish AFTER the payload

    # run_serve_loop's finally block checks m._event.is_set()
    @property
    def _event(self):
        return self

    def is_set(self) -> bool:
        return int(self._ring["meta"][self._slot][M_REP]) == self._gen


class ShmMailbox:
    """Per-shard server-side ring drain, mirroring ``Mailbox``'s drain /
    drain_nowait / depth surface.  FIFO is the global reservation order:
    the drain takes only the CONTIGUOUS published prefix (a reserved but
    not-yet-published slot — a writer mid-copy — blocks everything
    behind it, preserving cross-shard order)."""

    def __init__(self, layout: ShmLayout, buf, sid: int):
        self.layout = layout
        self.sid = sid
        self.ctl = layout.ctl_i(buf)
        self.ring = layout.ring(buf, sid)
        self._ridx_cell = C_CTL + sid

    @property
    def depth(self) -> int:
        """Reserved-but-undrained count — two lock-free int64 loads
        (the ``Mailbox.depth`` sampler contract)."""
        return max(0, int(self.ctl[C_RSV]) - int(self.ctl[self._ridx_cell]))

    def __len__(self) -> int:
        return self.depth

    def _published(self, idx: int) -> bool:
        cap = self.layout.cap
        return (int(self.ring["meta"][idx % cap][M_REQ])
                == idx // cap + 1)

    def _take(self, ridx: int, k: int) -> list:
        cap, tele = self.layout.cap, self.layout.telemetry
        out = [
            _ShmMsg(ridx + j, self.ring, (ridx + j) % cap,
                    (ridx + j) // cap + 1, tele)
            for j in range(k)
        ]
        self.ctl[self._ridx_cell] = ridx + k
        return out

    def drain(self, max_k: int, stop, timeout: float = 0.05,
              pow2: bool = False) -> list:
        ridx = int(self.ctl[self._ridx_cell])
        spins = 0
        while not self._published(ridx):
            if stop.is_set():
                return []
            spins = _pause(spins)
        k = 1
        while k < max_k and self._published(ridx + k):
            k += 1
        if pow2:
            k = 1 << (k.bit_length() - 1)
        return self._take(ridx, k)

    def drain_nowait(self) -> list:
        ridx = int(self.ctl[self._ridx_cell])
        k = 0
        while self._published(ridx + k):
            k += 1
        return self._take(ridx, k) if k else []


class ShmFanout:
    """Worker-side fan-out: one reservation under the shared lock orders
    the message on EVERY shard ring (the atomic-fanout contract), then
    the slot wait / payload copy / publish run out of lock.  The
    ``con_gen`` wait doubles as bounded-mailbox back-pressure: a worker
    cannot overwrite a slot whose previous occupant is still unserved or
    unconsumed."""

    def __init__(self, layout: ShmLayout, buf, lock):
        self.layout = layout
        self.lock = lock
        self.ctl = layout.ctl_i(buf)
        self.rings = [layout.ring(buf, s) for s in range(layout.shards)]

    def _reply_ready(self, token) -> bool:
        """True when every shard has published its reply for ``token`` —
        its ``rpc_await`` will complete without spinning."""
        slot, gen = token
        return all(int(self.rings[s]["meta"][slot][M_REP]) == gen
                   for s in range(self.layout.shards))

    def rpc_post(self, wid: int, grads, views, view_step: int,
                 t_send: float, stop: _ShmStop, *, pending=None,
                 on_settle=None, rpc_timeout=None):
        """The push half of the RPC: reserve a global index, copy the
        payload into every shard ring and publish — WITHOUT waiting for
        the replies.  Returns an opaque (slot, gen) token for
        ``rpc_await``, or None on shutdown.  Worker pull-ahead posts the
        next push before settling the previous one, so the RPC round
        trip hides behind the next gradient compute.

        ``pending`` (the caller's FIFO deque of posted-but-unsettled
        tokens) is REQUIRED for deadlock freedom whenever the caller
        keeps tokens in flight across posts: slots are assigned by a
        global counter, so the reserved slot's previous occupant can be
        one of the caller's OWN pending tokens — which only the caller's
        ``rpc_await`` can consume — or another blocked worker's, closing
        a wait cycle.  While spinning for the slot to free, the post
        therefore settles the caller's pending tokens oldest-first as
        soon as their replies are ready (a non-blocking check, so a
        reply held up by an unpublished earlier slot never converts this
        spin into an await), reporting each result through
        ``on_settle(out)``.  A blocked poster thus never sits on
        consumable tokens, which unwinds self-collisions and
        cross-worker cycles alike.  ``rpc_timeout`` (seconds) bounds the
        spin so a genuinely wedged slot raises TimeoutError instead of
        hanging."""
        lay = self.layout
        cap = lay.cap
        with self.lock:
            idx = int(self.ctl[C_RSV])
            self.ctl[C_RSV] = idx + 1
        slot, gen = idx % cap, idx // cap + 1
        # wait for the slot's previous occupant to be fully consumed
        deadline = (time.monotonic() + rpc_timeout
                    if rpc_timeout is not None else None)
        spins = 0
        for s in range(lay.shards):
            meta = self.rings[s]["meta"][slot]
            while int(meta[M_CON]) != gen - 1:
                if stop.is_set():
                    return None        # slot stays unpublished: see module doc
                if pending and self._reply_ready(pending[0]):
                    out = self.rpc_await(pending.popleft(), wid, stop,
                                         rpc_timeout or 1.0)
                    if on_settle is not None:
                        on_settle(out)
                    continue
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"worker {wid}: ring slot {slot} not freed in "
                        f"{rpc_timeout}s (previous occupant never "
                        f"consumed)")
                spins = _pause(spins)
        for s in range(lay.shards):
            ring = self.rings[s]
            meta = ring["meta"][slot]
            np.copyto(ring["grad"][slot], np.asarray(grads[s]))
            if lay.telemetry:
                np.copyto(ring["view"][slot], np.asarray(views[s]))
            meta[M_WID] = wid
            meta[M_VSTEP] = view_step
            ring["tsend"][slot] = t_send
            meta[M_REQ] = gen          # publish AFTER the payload
        return (slot, gen)

    def rpc_await(self, token, wid: int, stop: _ShmStop,
                  rpc_timeout: float):
        """The pull half: wait for every shard's reply to a posted
        token, copy the view slices out and free the slot.  Returns
        (views, step) or None on shutdown / rejection; raises
        TimeoutError like ``GradMsg.wait_reply``."""
        lay = self.layout
        slot, gen = token
        deadline = time.monotonic() + rpc_timeout
        stop_seen = None
        for s in range(lay.shards):
            meta = self.rings[s]["meta"][slot]
            spins = 0
            while int(meta[M_REP]) != gen:
                now = time.monotonic()
                if now > deadline:
                    raise TimeoutError(
                        f"worker {wid}: no shard-{s} reply in "
                        f"{rpc_timeout}s")
                if stop.is_set():
                    if stop_seen is None:
                        stop_seen = now
                    elif now - stop_seen > _STOP_GRACE:
                        return None
                spins = _pause(spins)
        ok = all(int(self.rings[s]["meta"][slot][M_ROK])
                 for s in range(lay.shards))
        out_views = tuple(np.array(self.rings[s]["rep"][slot])
                          for s in range(lay.shards))
        step = int(self.rings[0]["meta"][slot][M_RSTEP])
        for s in range(lay.shards):   # free the slot for reuse
            self.rings[s]["meta"][slot][M_CON] = gen
        return (out_views, step) if ok else None

    def rpc(self, wid: int, grads, views, view_step: int, t_send: float,
            stop: _ShmStop, rpc_timeout: float):
        """Fused push-pull across all shards (the synchronous depth-0
        composition of ``rpc_post`` + ``rpc_await``).  Returns
        (views, step) — range-ordered tuple of fresh per-shard view
        copies — or None on shutdown / rejection.  Raises TimeoutError
        like ``GradMsg.wait_reply``."""
        token = self.rpc_post(wid, grads, views, view_step, t_send, stop,
                              rpc_timeout=rpc_timeout)
        if token is None:
            return None
        return self.rpc_await(token, wid, stop, rpc_timeout)


def _attach(name: str):
    """Attach the block in a child without the resource tracker adopting
    it (bpo-38119: a tracked attachment would unlink the segment when
    the FIRST child exits, yanking it from under the cluster)."""
    from multiprocessing import resource_tracker, shared_memory
    orig = resource_tracker.register
    resource_tracker.register = lambda *a, **k: None
    try:
        shm = shared_memory.SharedMemory(name=name)
    finally:
        resource_tracker.register = orig
    return shm


def _gate_acquire(ctl, wid: int, n: int, stop: _ShmStop) -> bool:
    spins = 0
    while int(ctl[C_TURN]) % n != wid:
        if stop.is_set():
            return False
        spins = _pause(spins)
    return True


# =====================================================================
# server child
# =====================================================================
class _ProcServer:
    """One shard server inside its own process: the ``_ShardServer``
    serve surface (``run_serve_loop`` duck type) with ``applied`` /
    ``busy_s`` mirrored into shared control cells so the parent's
    publisher and the worker children read them lock-free.  Telemetry
    partials and eval snapshots are recorded locally (keyed by the
    global ring index) and shipped over the pipe after the run."""

    def __init__(self, sid, fa, state, mailbox, stop, *, total, coalesce,
                 telemetry, eval_boundary, eval_every, has_eval,
                 injector, steady_mark, metrics, ctl_i, ctl_f):
        import jax
        self.sid = sid
        self.fa = fa
        self.state = state
        self.mailbox = mailbox
        self.stop = stop
        self.total = total
        self.coalesce = max(1, coalesce)
        self.telemetry = telemetry
        self.eval_boundary = eval_boundary
        self.eval_every = eval_every
        self.has_eval = has_eval
        self.injector = injector
        self.error = None
        self._step = 0
        self._fused = {}
        self._send_jit = jax.jit(fa.send_flat)
        self._view_rows_jit = {}
        self.coalesce_counts = {}
        self.obs_cat = "shard"
        self.metrics = metrics
        self._steady_mark = steady_mark
        self._ctl_i = ctl_i
        self._ctl_f = ctl_f
        self.tele_rows = []            # (idx, wid, step, lag, t, d2, g2)
        self.eval_rows = []            # (watermark, t, theta rows copy)
        # stacked-wire staging: shm grad/view slices are memcpy'd into
        # these pinned host buffers so each batch costs ONE device
        # transfer (k, rows, 128) instead of k transfers + in-jit stack
        rows = int(state["theta"].shape[-2])
        self._gstage = np.empty((self.coalesce, rows, 128), np.float32)
        self._vstage = (np.empty_like(self._gstage) if telemetry
                        else None)
        # deferred telemetry spool: device-side d2/g2 plus host metas,
        # converted to floats only at eval watermarks / run end so the
        # steady-state serve loop never blocks on a device sync
        self._tele_spool = []
        self._tele_cap = 64

    # shared-cell mirrors (single writer: this process)
    @property
    def applied(self) -> int:
        return int(self._ctl_i[C_CTL + self.mailbox.layout.shards
                               + self.sid])

    @applied.setter
    def applied(self, v: int):
        self._ctl_i[C_CTL + self.mailbox.layout.shards + self.sid] = v

    @property
    def busy_s(self) -> float:
        return float(self._ctl_f[F_CTL + self.sid])

    @busy_s.setter
    def busy_s(self, v: float):
        self._ctl_f[F_CTL + self.sid] = v

    @property
    def slab_info(self):
        st = self.state
        if "v" not in st:
            return None
        n_slabs = 2 if "sent" in st else 1
        return (int(st["v"].shape[0]),
                2 * int(st["v"].shape[-2]) * n_slabs)

    def _get_fused(self, k: int, telemetry: bool):
        import jax
        import jax.numpy as jnp
        fn = self._fused.get((k, telemetry))
        if fn is not None:
            return fn
        fa = self.fa

        def fused(flat, ids, nows, g, views):
            # g and views arrive pre-stacked (k, rows, 128): the serve
            # loop stages the shm grads into one pinned host buffer and
            # ships ONE device transfer per batch instead of k
            flat, hats, pres = fa.apply_batch(flat, ids, g, nows,
                                              telemetry=telemetry)
            out_views = tuple(hats[j] for j in range(k))
            if telemetry:
                d = pres - views
                return (flat, out_views, jnp.sum(d * d, axis=(1, 2)),
                        jnp.sum(g * g, axis=(1, 2)))
            return flat, out_views, None, None

        fn = jax.jit(fused, donate_argnums=(0,))
        self._fused[(k, telemetry)] = fn
        return fn

    def warm(self):
        import jax
        import jax.numpy as jnp
        view = self.state["theta"]
        k = 1
        while k <= self.coalesce:
            fn = self._get_fused(k, self.telemetry)
            g = jnp.zeros((k,) + view.shape, view.dtype)
            out = fn(jax.tree.map(jnp.copy, self.state),
                     jnp.zeros((k,), jnp.int32),
                     jnp.zeros((k,), jnp.float32),
                     g,
                     jnp.broadcast_to(view, g.shape) if self.telemetry
                     else None)
            jax.block_until_ready(jax.tree.leaves(out[0])[0])
            k *= 2

    def _apply(self, work: list):
        import jax.numpy as jnp
        k = len(work)
        telemetry = self.telemetry
        fn = self._get_fused(k, telemetry)
        ids = jnp.asarray([m.worker_id for m in work], jnp.int32)
        nows = jnp.asarray([m.t_send for m in work], jnp.float32)
        # stage the zero-copy shm slices into the pinned host buffer:
        # one contiguous (k, rows, 128) transfer replaces k small ones
        for j, m in enumerate(work):
            np.copyto(self._gstage[j], m.grad)
            if telemetry:
                np.copyto(self._vstage[j], m.view)
        grads = jnp.asarray(self._gstage[:k])
        views = jnp.asarray(self._vstage[:k]) if telemetry else None
        t0 = self._step
        st, out_views, d2, g2 = fn(self.state, ids, nows, grads, views)
        self.state = st
        self._step = t0 + k
        if telemetry:
            # spool device-side; metas capture everything the flush
            # needs so the shipped rows are byte-identical to eager ones
            self._tele_spool.append(
                (t0, [(m.idx, m.worker_id, m.view_step, m.t_send)
                      for m in work], d2, g2))
        from .mailbox import Reply
        evals = []
        for j, m in enumerate(work):
            self.applied += 1
            if self.sid == 0 and self.applied == self._steady_mark:
                self._ctl_f[F_STEADY] = time.monotonic()
            m.respond(Reply(view=out_views[j], step=t0 + j + 1))
            if self.has_eval and (self.applied % self.eval_every == 0
                                  or self.applied == self.total):
                evals.append((m.t_send, self.applied))
        if telemetry and (evals or len(self._tele_spool) >= self._tele_cap):
            self._flush_telemetry()
        for t_ev, step_ev in evals:
            # np.array(copy): np.asarray can alias the donated device
            # buffer on CPU, which the next apply would overwrite
            self.eval_rows.append((step_ev, t_ev,
                                   np.array(self.state["theta"])))

    def _flush_telemetry(self):
        """Convert the spooled device partials to tele_rows floats (the
        only host sync on the telemetry path)."""
        for t0, metas, d2, g2 in self._tele_spool:
            d2 = np.asarray(d2)
            g2 = np.asarray(g2)
            for j, (idx, wid, vstep, t_send) in enumerate(metas):
                self.tele_rows.append(
                    (idx, wid, t0 + j + 1, t0 + j - vstep, t_send,
                     float(d2[j]), float(g2[j])))
        self._tele_spool.clear()

    def _pull_reply(self, m) -> int:
        import jax.numpy as jnp
        from .mailbox import Reply
        view, self.state = self._send_jit(self.state,
                                          jnp.int32(m.worker_id))
        m.respond(Reply(view=view, step=self._step))
        return int(view.shape[-2])


def server_main(conn, shm_name, layout, sid, job):
    """Shard-server child entry point (spawn target; module-level for
    picklability)."""
    shm = None
    try:
        import jax.numpy as jnp
        from ..core.flat import FlatSpec
        from ..kernels.flat_update import FlatAlgorithm
        from ..obs.metrics import MetricsRegistry, serve_instruments
        from ..launch.cache import enable_compile_cache
        from .faults import FaultInjector
        from .master import run_serve_loop

        enable_compile_cache()
        shm = _attach(shm_name)
        buf = shm.buf
        ctl_i = layout.ctl_i(buf)
        ctl_f = layout.ctl_f(buf)
        stop = _ShmStop(ctl_i)
        mailbox = ShmMailbox(layout, buf, sid)
        fa = FlatAlgorithm(job["algo"])
        fa.spec = FlatSpec.from_tree(job["params0"])
        state = {k: jnp.asarray(v) for k, v in job["state"].items()}
        injector = None
        if job["faults"] is not None:
            injector = FaultInjector(job["faults"], 0,
                                     job["mean_iter_time"], shard_id=sid)
        reg = MetricsRegistry()
        server = _ProcServer(
            sid, fa, state, mailbox, stop, total=job["total"],
            coalesce=job["coalesce"], telemetry=job["telemetry"],
            eval_boundary=job["eval_boundary"],
            eval_every=job["eval_every"], has_eval=job["has_eval"],
            injector=injector, steady_mark=job["steady_mark"],
            metrics=serve_instruments(reg), ctl_i=ctl_i, ctl_f=ctl_f)
        server.warm()
        conn.send(("ready", None))
        try:
            run_serve_loop(server)
        finally:
            # best-effort spool flush even when the serve loop raises,
            # mirroring Master.serve: spooled telemetry outlives errors
            if server.telemetry:
                try:
                    server._flush_telemetry()
                except BaseException as e:  # noqa: BLE001 - keep 1st error
                    if server.error is None:
                        server.error = e

        def _reject_until_shutdown():
            # reject stragglers until the parent confirms every worker
            # is down (the threaded runtime's reject_pending loop)
            while not ctl_i[C_SHUTDOWN]:
                for m in mailbox.drain_nowait():
                    m.respond(None)
                time.sleep(1e-3)
            for m in mailbox.drain_nowait():
                m.respond(None)

        if server.error is not None:
            stop.set()
            conn.send(("error", {
                "name": f"shard-{sid}",
                "trace": "".join(traceback.format_exception(
                    type(server.error), server.error,
                    server.error.__traceback__))}))
            _reject_until_shutdown()
            conn.close()
            sys.exit(1)
        _reject_until_shutdown()
        mx = server.metrics
        conn.send(("done", {
            "state": {k: np.asarray(v) for k, v in server.state.items()},
            "applied": server.applied,
            "busy_s": server.busy_s,
            "step": server._step,
            "coalesce_counts": server.coalesce_counts,
            "tele_rows": server.tele_rows,
            "eval_rows": server.eval_rows,
            "instruments": {
                "drain_k": mx.drain_k._merged(),
                "pulls": mx.pulls.value,
                "overflow": mx.overflow.value,
                "slab_rows_streamed": mx.slab_rows_streamed.value,
                "slab_rows_total": mx.slab_rows_total.value,
                "pull_rows": mx.pull_rows.value,
            }}))
        conn.close()
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 - shipped to the parent
        try:
            if shm is not None:
                layout.ctl_i(shm.buf)[C_STOP] = 1
            conn.send(("error", {"name": f"shard-{sid}",
                                 "trace": traceback.format_exc()}))
            conn.close()
        except Exception:  # noqa: BLE001
            pass
        sys.exit(1)
    finally:
        if shm is not None:
            try:
                shm.close()       # numpy views may still pin the buffer
            except BufferError:
                pass


# =====================================================================
# worker child
# =====================================================================
def worker_main(conn, shm_name, layout, lock, wid, job):
    """Worker child entry point: the ``Worker._run_live`` loop against
    the shared-memory fan-out (spawn target; module-level for
    picklability)."""
    shm = None
    try:
        import jax
        from ..core.flat import FlatSpec
        from ..launch.cache import enable_compile_cache
        from .faults import FaultInjector

        enable_compile_cache()
        shm = _attach(shm_name)
        buf = shm.buf
        ctl_i = layout.ctl_i(buf)
        ctl_f = layout.ctl_f(buf)
        stop = _ShmStop(ctl_i)
        fanout = ShmFanout(layout, buf, lock)
        n = layout.num_workers
        S = layout.shards
        grad_fn = job["grad_fn"]
        next_batch = job["next_batch"]
        spec = FlatSpec.from_tree(job["params0"])
        subs = [spec.subspec(r0, r1) for r0, r1 in layout.ranges]

        # the fused backward->wire emit (one jit: gather -> unpack ->
        # backward -> pack_fused -> per-shard scatter).  No donation
        # here: views arrive as fresh host copies out of the shm ring,
        # so there is no device buffer to reuse
        def _sharded_grad(fv, batch):
            g = spec.pack_fused(
                grad_fn(spec.unpack(spec.concat_rows(fv)), batch))
            return tuple(sub.take(g) for sub in subs)

        grad_jit = jax.jit(_sharded_grad)
        views = tuple(job["init_view"])
        view_step = job["init_step"]
        injector = None
        if job["faults"] is not None:
            injector = FaultInjector(job["faults"], n,
                                     job["mean_iter_time"])
        draw = None
        if job["mode"] == "paced":
            import dataclasses as _dc
            em = _dc.replace(job["exec_model"],
                             seed=job["exec_model"].seed
                             + 1000003 * (wid + 1))
            sampler = em.sampler(n)
            draw = (lambda: sampler(wid))
        t0 = float(ctl_f[F_T0])
        scale = job["time_scale"]
        if job["mode"] == "paced":
            now_fn = (lambda: (time.monotonic() - t0) / scale)
        else:
            now_fn = (lambda: time.monotonic() - t0)
        pin = job["pin_schedule"]
        total = job["total"]
        depth = job.get("pipeline_depth", 0)
        applied_cells = ctl_i[C_CTL + S:C_CTL + 2 * S]
        pending = deque()   # pull-ahead: posted-but-unsettled tokens
        grads_sent = 0
        live = True

        def _adopt(out):
            # settle bookkeeping shared by the in-order awaits and the
            # ready-settles rpc_post performs while blocked on a slot
            nonlocal views, view_step, grads_sent, live
            if out is None:
                live = False        # end-of-run rejection / shutdown
            else:
                views, view_step = out
                grads_sent += 1

        counter = 0
        while (not stop.is_set()
               and int(applied_cells.min()) < total):
            stall = injector.stall(wid) if injector is not None else 0.0
            dt = stall + (draw() if draw is not None else 0.0)
            if dt > 0.0 and stop.wait(dt * scale):
                break
            if pin and not _gate_acquire(ctl_i, wid, n, stop):
                break
            try:
                batch = next_batch(wid, counter)
                counter += 1
                grads = grad_jit(views, batch)
                if depth == 0:
                    out = fanout.rpc(wid, grads,
                                     views if job["telemetry"] else None,
                                     view_step, now_fn(), stop,
                                     job["rpc_timeout"])
                else:
                    # pull-ahead: publish the push and move on; the
                    # reply is collected only once more than `depth`
                    # RPCs are outstanding.  Passing `pending` lets a
                    # blocked post settle ready replies in place — the
                    # global slot counter can park this worker behind
                    # its OWN unconsumed token (or another blocked
                    # worker's), which only these settles can free
                    tok = fanout.rpc_post(
                        wid, grads, views if job["telemetry"] else None,
                        view_step, now_fn(), stop,
                        pending=pending, on_settle=_adopt,
                        rpc_timeout=job["rpc_timeout"])
            finally:
                if pin:
                    ctl_i[C_TURN] += 1
            if depth == 0:
                if out is None:
                    break
                views, view_step = out
                grads_sent += 1
                continue
            if tok is None:
                break
            pending.append(tok)
            while live and len(pending) > depth:
                _adopt(fanout.rpc_await(pending.popleft(), wid, stop,
                                        job["rpc_timeout"]))
            if not live:
                break
        # settle stragglers so every applied grad is counted (end-of-run
        # rejections resolve to None)
        while pending:
            out = fanout.rpc_await(pending.popleft(), wid, stop,
                                   job["rpc_timeout"])
            if out is not None:
                grads_sent += 1
        conn.send(("done", {"grads_sent": grads_sent}))
        conn.close()
    except BaseException:  # noqa: BLE001 - shipped to the parent
        try:
            if shm is not None:
                layout.ctl_i(shm.buf)[C_STOP] = 1
            conn.send(("error", {"name": f"worker-{wid}",
                                 "trace": traceback.format_exc()}))
            conn.close()
        except Exception:  # noqa: BLE001
            pass
        sys.exit(1)
    finally:
        if shm is not None:
            try:
                shm.close()
            except BufferError:
                pass


class RemoteChildError(RuntimeError):
    """A child process failed; carries its formatted traceback."""

    def __init__(self, name: str, trace: str):
        super().__init__(f"{name} failed:\n{trace}")
        self.child = name


# =====================================================================
# parent orchestrator
# =====================================================================
def _check_picklable(grad_fn, next_batch):
    for label, fn in (("grad_fn", grad_fn), ("next_batch", next_batch)):
        try:
            pickle.dumps(fn)
        except Exception as e:  # noqa: BLE001
            raise ValueError(
                f"backend='process' requires a picklable {label} "
                f"(children re-import and re-jit under spawn); got "
                f"{fn!r}: {e}.  Use a module-level function or a "
                f"callable class (repro.models.toy.ClassifierGradFn, "
                f"repro.models.api.ModelGradFn) instead of a "
                f"closure.") from e


def require_host_backend():
    """Refuse to start JAX children from a process that holds a TPU.

    The parent runs JAX before it spawns, and every child imports JAX;
    only one process may hold a TPU chip, so on a TPU host the children
    would fail or hang waiting for it."""
    import jax
    if jax.default_backend() == "tpu":
        raise ValueError(
            "backend='process' starts JAX in the parent and in every "
            "child process, but only one process may hold a TPU chip; "
            "use the threaded backend (backend='thread') on a TPU host")


def validate_process_config(algo, cfg):
    """The process backend's support matrix (README "Backends")."""
    from ..kernels.flat_update import family_spec_for, kernel_eligible
    require_host_backend()
    if cfg.mode == "deterministic":
        raise ValueError("backend='process' supports live modes only "
                         "(paced/free); deterministic replay needs the "
                         "threaded backend's virtual clock")
    if cfg.use_kernel is False:
        raise ValueError("backend='process' runs the flat kernel wire "
                         "format; use_kernel must not be False")
    if not kernel_eligible(algo):
        raise ValueError(f"backend='process' requires a kernel-eligible "
                         f"algorithm, got {algo.name!r}")
    fam = family_spec_for(algo)
    if fam.gap_aware and cfg.shards > 1:
        raise ValueError("gap-aware members need the cross-shard norm "
                         "exchange (threads-only); use shards=1 on the "
                         "process backend")
    if cfg.faults is not None and cfg.faults.any_dropout:
        raise ValueError("dropout/rejoin is not supported on the "
                         "process backend (stalls and reorder are)")
    if cfg.hot_rows is not None:
        raise ValueError("hot_rows pulls are not supported on the "
                         "process backend")
    if cfg.rebalance or cfg.shard_ranges is not None:
        raise ValueError("row rebalancing / custom shard_ranges are not "
                         "supported on the process backend")
    if cfg.pin_schedule and cfg.faults is not None \
            and cfg.faults.any_dropout:
        raise ValueError("pin_schedule cannot combine with dropout")
    if cfg.pipeline_depth > 0:
        cap = cfg.mailbox_capacity or max(4, 2 * cfg.num_workers)
        need = (cfg.pipeline_depth + 1) * cfg.num_workers
        if need > cap:
            raise ValueError(
                f"pipeline_depth={cfg.pipeline_depth} can keep "
                f"{need} RPCs in flight but the shm ring holds only "
                f"{cap} slots; raise mailbox_capacity to at least "
                f"{need}")


def run_cluster_procs(algo, grad_fn, params0, next_batch, cfg,
                      eval_fn=None, stats_out=None, metrics=None):
    """Process-backend twin of the threaded ``run_cluster`` body: same
    arguments, same ``History`` result, same stats keys."""
    import multiprocessing as mp
    from multiprocessing import shared_memory

    import jax
    import jax.numpy as jnp

    from ..core.metrics import History
    from ..kernels.flat_update import (FlatAlgorithm, family_spec_for,
                                       merge_flat, slice_flat)
    from ..obs import trace
    from ..obs.metrics import (SnapshotPublisher, history_observer,
                               serve_instruments)
    from .mailbox import Reply  # noqa: F401 - wire-format anchor

    validate_process_config(algo, cfg)
    _check_picklable(grad_fn, next_batch)
    n = cfg.num_workers
    S = cfg.shards
    fam = family_spec_for(algo)
    fa = FlatAlgorithm(algo)
    flat = fa.adopt(algo.init(params0, n))
    spec = fa.spec
    ranges = spec.row_ranges(S)
    history = History()
    telemetry = cfg.record_telemetry
    params0_np = jax.tree.map(np.asarray, params0)

    # warm-up sends in worker order on sliced states (the threaded
    # sharded master's initial_view nesting, so sent-slab stamps match)
    send_jit = jax.jit(fa.send_flat)
    shard_states = [slice_flat(flat, r0, r1) for r0, r1 in ranges]
    init_views = []
    init_step = 0
    for i in range(n):
        vs = []
        for s in range(S):
            view, shard_states[s] = send_jit(shard_states[s],
                                             jnp.int32(i))
            vs.append(np.asarray(view))
        init_views.append(tuple(vs))

    cap = cfg.mailbox_capacity or max(4, 2 * n)
    layout = ShmLayout(ranges, n, cap, telemetry)
    ctx = mp.get_context("spawn")
    shm = shared_memory.SharedMemory(create=True, size=layout.total)
    lock = ctx.Lock()
    ctl_i = layout.ctl_i(shm.buf)
    ctl_f = layout.ctl_f(shm.buf)
    ctl_i[:] = 0
    ctl_f[:] = 0.0
    stop = _ShmStop(ctl_i)
    mean_iter = cfg.exec_model.batch_size
    steady_mark = max(1, cfg.total_grads // 5)
    coalesce = cfg.coalesce
    eval_boundary = cfg.eval_every if eval_fn is not None else 0
    eval_jit = jax.jit(eval_fn) if eval_fn is not None else None
    inv_sqrt_p = 1.0 / math.sqrt(spec.n_elems)
    sent_family = fam.stateful_send

    server_job_base = dict(
        algo=algo, params0=params0_np, total=cfg.total_grads,
        coalesce=coalesce, telemetry=telemetry,
        eval_boundary=eval_boundary, eval_every=max(1, cfg.eval_every),
        has_eval=eval_fn is not None, faults=cfg.faults,
        mean_iter_time=mean_iter, steady_mark=steady_mark)
    worker_job_base = dict(
        grad_fn=grad_fn, next_batch=next_batch, params0=params0_np,
        faults=cfg.faults, mean_iter_time=mean_iter, mode=cfg.mode,
        exec_model=cfg.exec_model, time_scale=cfg.time_scale,
        telemetry=telemetry, rpc_timeout=cfg.rpc_timeout,
        pin_schedule=cfg.pin_schedule, total=cfg.total_grads,
        pipeline_depth=cfg.pipeline_depth)

    servers, workers = [], []
    server_conns, worker_conns = [], []
    payloads: dict[int, dict] = {}      # sid -> server done payload
    worker_done: dict[int, dict] = {}
    errors: list[tuple[str, str]] = []  # (name, trace)
    publisher = None
    t0_wall = time.perf_counter()

    def _poll(conns, procs, names, bank):
        """Drain one round of child messages into ``bank`` (index ->
        payload dict).  A child that died without reporting lands in the
        bank as an error entry — the monitor accounts for it immediately
        instead of waiting out a deadline on a corpse."""
        for i, (c, p) in enumerate(zip(conns, procs)):
            if c is not None:
                try:
                    while c.poll(0):
                        kind, data = c.recv()
                        if kind == "ready":
                            bank[i] = {"ready": True}
                        elif kind == "done":
                            bank[i] = data
                            conns[i] = None
                        else:
                            errors.append((data["name"], data["trace"]))
                            bank[i] = {"error": data["name"]}
                            conns[i] = None
                except (EOFError, OSError):
                    conns[i] = None
            settled = i in bank and not bank[i].get("ready")
            if settled or p.is_alive():
                continue
            if conns[i] is not None:
                # the process is gone with its pipe still open: one
                # grace recv for a message that was in flight when it
                # exited (poll() is also true at EOF, so only recv can
                # tell a straggler from a closed pipe)
                try:
                    if c.poll(0.2):
                        kind, data = c.recv()
                        if kind == "done":
                            bank[i] = data
                            conns[i] = None
                            continue
                        if kind == "error":
                            errors.append((data["name"], data["trace"]))
                            bank[i] = {"error": data["name"]}
                            conns[i] = None
                            continue
                        bank[i] = {"ready": True}
                except (EOFError, OSError):
                    pass
                conns[i] = None
            errors.append((names[i],
                           f"{names[i]} process died without "
                           f"reporting an error "
                           f"(exit code {p.exitcode})"))
            bank[i] = {"error": names[i]}

    try:
        for sid in range(S):
            r0, r1 = ranges[sid]
            job = dict(server_job_base,
                       state={k: np.asarray(v)
                              for k, v in shard_states[sid].items()})
            pr, pw = ctx.Pipe(duplex=False)
            p = ctx.Process(target=server_main,
                            args=(pw, shm.name, layout, sid, job),
                            name=f"ps-proc-shard-{sid}", daemon=True)
            p.start()
            pw.close()
            servers.append(p)
            server_conns.append(pr)

        names_s = [f"shard-{s}" for s in range(S)]
        names_w = [f"worker-{w}" for w in range(n)]

        # wait for every shard server to finish warm-up compiles
        deadline = time.monotonic() + max(cfg.rpc_timeout, 300.0)
        while (sum(1 for v in payloads.values() if v.get("ready")) < S
               and not errors):
            _poll(server_conns, servers, names_s, payloads)
            if time.monotonic() > deadline:
                raise RuntimeError("process backend: shard servers "
                                   "failed to become ready in time")
            time.sleep(0.01)
        if errors:
            raise RuntimeError(
                f"cluster run failed in {errors[0][0]} "
                f"({len(errors)} process error(s))") from RemoteChildError(
                *errors[0])

        if metrics is not None:
            history.observer = history_observer(metrics)
        if metrics is not None or trace.enabled:
            parent_boxes = [ShmMailbox(layout, shm.buf, s)
                            for s in range(S)]
            sources = {}
            for s in range(S):
                sources[f"mailbox_depth/shard{s}"] = \
                    (lambda mb=parent_boxes[s]: mb.depth)
                sources[f"busy_s/shard{s}"] = \
                    (lambda s=s: float(ctl_f[F_CTL + s]))
            publisher = SnapshotPublisher(sources, registry=metrics)
            publisher.start()

        ctl_f[F_T0] = time.monotonic()
        t0_wall = time.perf_counter()
        for wid in range(n):
            job = dict(worker_job_base, init_view=init_views[wid],
                       init_step=init_step)
            pr, pw = ctx.Pipe(duplex=False)
            p = ctx.Process(target=worker_main,
                            args=(pw, shm.name, layout, lock, wid, job),
                            name=f"ps-proc-worker-{wid}", daemon=True)
            p.start()
            pw.close()
            workers.append(p)
            worker_conns.append(pr)

        applied_cells = ctl_i[C_CTL + S:C_CTL + 2 * S]
        stop_deadline = None
        while len(worker_done) < n:
            _poll(worker_conns, workers, names_w, worker_done)
            _poll(server_conns, servers, names_s, payloads)
            if errors:
                stop.set()
            if int(applied_cells.min()) >= cfg.total_grads:
                stop.set()    # release pin-gate / drain waiters
            if stop.is_set() and stop_deadline is None:
                stop_deadline = (time.monotonic()
                                 + max(cfg.rpc_timeout, 10.0))
            if stop_deadline is not None \
                    and time.monotonic() > stop_deadline:
                for name, p in zip(names_w, workers):
                    if p.is_alive():
                        p.terminate()
                        errors.append((name, f"{name} failed to shut "
                                             f"down"))
                break
            if len(worker_done) < n:
                time.sleep(0.005)

        # all workers accounted for (or terminated): let servers finish
        stop.set()
        ctl_i[C_SHUTDOWN] = 1
        t_end = time.perf_counter()
        t_end_mono = time.monotonic()
        steady_mono = float(ctl_f[F_STEADY])

        def _servers_settled():
            return all(
                s in payloads and ("state" in payloads[s]
                                   or "error" in payloads[s])
                for s in range(S))

        deadline = time.monotonic() + max(cfg.rpc_timeout, 30.0)
        while not _servers_settled():
            _poll(server_conns, servers, names_s, payloads)
            if time.monotonic() > deadline:
                break
            if not _servers_settled():
                time.sleep(0.005)
        for p in workers + servers:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
    finally:
        if publisher is not None:
            publisher.stop()
        for p in workers + servers:
            if p.is_alive():
                p.terminate()
        try:
            shm.close()           # numpy views may still pin the buffer
        except BufferError:
            pass
        try:
            shm.unlink()
        except FileNotFoundError:
            pass

    if errors:
        name, tb = errors[0]
        raise RuntimeError(
            f"cluster run failed in {name} "
            f"({len(errors)} process error(s))") from RemoteChildError(
            name, tb)
    missing = [s for s in range(S)
               if "state" not in payloads.get(s, {})]
    if missing:
        raise RuntimeError(f"process backend: missing shard results "
                           f"for shards {missing}")

    applied = min(payloads[s]["applied"] for s in range(S))
    if applied != cfg.total_grads:
        raise RuntimeError(f"cluster stopped early: applied "
                           f"{applied}/{cfg.total_grads} gradients")

    # -- post-hoc merge: state, telemetry, evals, instruments ------------
    full_flat = merge_flat([
        {k: jnp.asarray(v) for k, v in payloads[s]["state"].items()}
        for s in range(S)])
    history.final_params = spec.unpack(full_flat["theta"])

    tele_dropped = 0
    if telemetry:
        groups: dict[int, list] = {}
        for s in range(S):
            for row in payloads[s]["tele_rows"]:
                groups.setdefault(row[0], []).append((s, row))
        # shard 0's apply order is the canonical History row order (the
        # threaded sharded master's completion order is similar-but-
        # racy; post-hoc we can afford the deterministic choice)
        for idx, wid, step, lag, t, _, _ in payloads[0]["tele_rows"]:
            parts = groups.get(idx, [])
            if len(parts) != S:
                tele_dropped += 1
                continue
            d2 = sum(r[5] for _, r in parts)
            g2 = sum(r[6] for _, r in parts)
            history.record(
                time=t, step=step, worker=wid, lag=lag,
                gap=math.sqrt(d2) * inv_sqrt_p,
                grad_norm=math.sqrt(g2),
                staleness=float(lag) if sent_family else float("nan"))
        # partial groups missing shard 0 entirely
        for idx, parts in groups.items():
            if len(parts) != S and not any(s == 0 for s, _ in parts):
                tele_dropped += 1

    if eval_jit is not None:
        slots: dict[int, dict] = {}
        for s in range(S):
            for step_ev, t_ev, rows in payloads[s]["eval_rows"]:
                slot = slots.setdefault(step_ev, {"thetas": {},
                                                  "t": None})
                slot["thetas"][s] = rows
                if s == 0:
                    slot["t"] = t_ev
        for step_ev in sorted(slots):
            slot = slots[step_ev]
            if len(slot["thetas"]) != S:
                continue
            theta = spec.concat_rows(
                [jnp.asarray(slot["thetas"][s]) for s in range(S)])
            out = eval_jit(spec.unpack(theta))
            loss, metric = (out if isinstance(out, tuple)
                            else (out, float("nan")))
            history.record_eval(time=slot["t"], step=step_ev,
                                loss=loss, metric=metric)

    if metrics is not None:
        mx = serve_instruments(metrics)
        for s in range(S):
            inst = payloads[s]["instruments"]
            counts, total_, cnt, lo, hi = inst["drain_k"]
            if cnt:
                mx.drain_k._cells[f"proc-shard{s}"] = \
                    [list(counts), total_, cnt, lo, hi]
            mx.pulls.add(inst["pulls"])
            mx.overflow.add(inst["overflow"])
            mx.slab_rows_streamed.add(inst["slab_rows_streamed"])
            mx.slab_rows_total.add(inst["slab_rows_total"])
            mx.pull_rows.add(inst["pull_rows"])
        if tele_dropped:
            mx.tele_dropped.add(tele_dropped)

    if stats_out is not None:
        coalesce_counts: dict[int, int] = {}
        for s in range(S):
            for k, c in payloads[s]["coalesce_counts"].items():
                coalesce_counts[k] = coalesce_counts.get(k, 0) + c
        applied_total = sum(k * v for k, v in coalesce_counts.items())
        busy = max(payloads[s]["busy_s"] for s in range(S))
        steady = None
        if 0.0 < steady_mono < t_end_mono:
            steady = ((applied - steady_mark)
                      / max(t_end_mono - steady_mono, 1e-9))
        stats_out.update(
            applied=applied,
            wall_s=t_end - t0_wall,
            updates_per_s=applied / max(t_end - t0_wall, 1e-9),
            steady_updates_per_s=steady,
            master_busy_s=busy,
            master_updates_per_s=applied / max(busy, 1e-9),
            coalesce_counts=dict(sorted(coalesce_counts.items())),
            mean_coalesce=(applied_total
                           / max(sum(coalesce_counts.values()), 1)),
            grads_per_worker={w: worker_done[w].get("grads_sent", 0)
                              for w in sorted(worker_done)},
            use_kernel=True,
            shards=S,
            backend="process",
            shard_applied=[payloads[s]["applied"] for s in range(S)],
            telemetry_dropped=tele_dropped,
        )
        if publisher is not None:
            stats_out["obs_series"] = publisher.series()
        if fa.lane is not None:
            stats_out["sent_staleness"] = [
                float(x) for x in np.asarray(fa.staleness(full_flat))]
        if fam.rate_weighted:
            from ..core.flat import RATE_INTERVAL, RATE_LANE
            stats_out["rate_intervals"] = [
                float(x) for x in np.asarray(
                    RATE_LANE.get(full_flat["rate"], RATE_INTERVAL))]
    return history
