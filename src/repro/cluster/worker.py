"""Worker thread: pull view -> compute gradient -> push -> repeat.

Pacing is pluggable:

* ``deterministic`` — the worker acquires its turn from the virtual clock
  (engine event order), so the whole cluster serializes into exactly the
  discrete-event schedule.
* ``paced``  — the worker sleeps a gamma-model execution time (scaled by
  ``time_scale``) before each push: wall-clock simulation fidelity.
* ``free``   — no pacing; the worker pushes as fast as it can compute
  (throughput mode — this is what fills the master's mailbox and makes
  coalesced receive pay off).

The push is a fused push-pull RPC: the reply carries the post-update view
(the engine's receive->send semantics), so a worker never computes two
gradients on the same view.

``pipeline_depth`` (live modes) turns the RPC into a pull-ahead
pipeline: the worker keeps up to ``depth`` pushes in flight and computes
its next gradient against the newest reply it HAS — the RPC round trip
overlaps with gradient compute instead of being dead time, at the cost
of exactly ``depth`` extra designed staleness (the paper's
asynchrony-begets-momentum regime, which DANA's look-ahead is built to
tame).  ``depth=0`` is today's fully synchronous push-pull, bit-exact.
Each ``GradMsg`` is its own reply slot (see ``mailbox``), so pull-ahead
needs no protocol change — the worker just defers ``wait_reply``.

The worker is oblivious to the master's layout: view and gradient are
whatever its ``grad_jit`` produces/consumes — a pytree (tree master), a
flat (R, 128) buffer (flat master), or a range-ordered tuple of row
slices (sharded master, where ``mailbox`` is the ``FanoutMailbox`` front
and one push fans out to every shard).

Donation contract (flat path): the runtime's fused grad jits unpack the
received view into model params, run the backward and emit the (R, 128)
wire in ONE jit, and may DONATE the view buffer to it
(``cluster.runtime`` gates this on telemetry off + ``pipeline_depth=0``
+ no ``hot_rows``).  Those are exactly the three behaviors below that
re-touch a view after ``grad`` runs — attaching it to the ``GradMsg``
telemetry, recomputing against a cached reply in the pull-ahead
pipeline, and ``merge_view`` patching hot rows — so under the gate the
view is dead the moment ``grad`` is called and XLA may reuse its
storage for the wire buffer.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable

from ..obs import trace
from .clock import VirtualClock
from .faults import FaultInjector
from .mailbox import GradMsg, Mailbox
from .master import Master


class TurnGate:
    """Round-robin message-schedule pin (``ClusterConfig.pin_schedule``).

    Worker ``wid`` may push only when ``turn % n == wid`` and advances the
    turn after its push completes, so the mailbox sees the exact sequence
    0, 1, ..., n-1, 0, 1, ... regardless of thread scheduling.  This makes
    live-mode runs schedule-deterministic — the process backend pins the
    same order through a shared-memory turn counter, which is what the
    cross-backend bit-exactness tests compare under."""

    def __init__(self, n: int, stop: threading.Event):
        self.n = n
        self.stop = stop
        self._turn = 0
        self._cond = threading.Condition()

    def acquire(self, wid: int) -> bool:
        with self._cond:
            while self._turn % self.n != wid:
                if self.stop.is_set():
                    return False
                self._cond.wait(timeout=0.05)
        return True

    def advance(self):
        with self._cond:
            self._turn += 1
            self._cond.notify_all()


class Worker(threading.Thread):
    def __init__(self, wid: int, *, master: Master, mailbox: Mailbox,
                 grad_jit: Callable, next_batch: Callable,
                 stop: threading.Event, mode: str,
                 init_view: tuple[Any, int],
                 clock: VirtualClock | None = None,
                 draw: Callable[[int], float] | None = None,
                 now_fn: Callable[[], float] | None = None,
                 time_scale: float = 1e-3,
                 injector: FaultInjector | None = None,
                 telemetry: bool = True, rpc_timeout: float = 120.0,
                 hot_rows: tuple[int, int] | None = None,
                 merge_view: Callable | None = None,
                 gate: TurnGate | None = None,
                 pipeline_depth: int = 0):
        super().__init__(name=f"ps-worker-{wid}", daemon=True)
        self.wid = wid
        self.master = master
        self.mailbox = mailbox
        self.grad_jit = grad_jit
        self.next_batch = next_batch
        self.stop = stop
        self.mode = mode
        self.clock = clock
        self.draw = draw
        self.now_fn = now_fn or (lambda: 0.0)
        self.time_scale = time_scale
        self.injector = injector
        self.telemetry = telemetry
        self.rpc_timeout = rpc_timeout
        # hot-row pulls: the (r0, r1) flat-row range this worker declares
        # hot — pull-only requests ask the master for just those rows and
        # ``merge_view`` patches the partial reply into the cached view
        # (both set together by the runtime; a master that cannot honor
        # the range replies with a full view and rows=None)
        self.hot_rows = (hot_rows if merge_view is not None else None)
        self.merge_view = merge_view
        self.gate = gate
        # pull-ahead: up to this many pushes stay in flight (live modes;
        # deterministic mode serializes through the virtual clock and
        # always runs depth 0)
        self.pipeline_depth = (0 if mode == "deterministic"
                               else max(0, pipeline_depth))
        self._pending: deque[GradMsg] = deque()
        self._view, self._view_step = init_view
        self.error: BaseException | None = None
        self.grads_sent = 0

    # -- thread entry ----------------------------------------------------
    def run(self):
        try:
            if self.mode == "deterministic":
                self._run_deterministic()
            else:
                self._run_live()
        except BaseException as e:  # noqa: BLE001 - reported by run_cluster
            self.error = e
            self.stop.set()
            if self.clock is not None:
                self.clock.stop()

    # -- pipelined RPC halves (pipeline_depth > 0) -----------------------
    def _post(self, grad, t_send: float, seq: int) -> GradMsg | None:
        """Enqueue one push without waiting for its reply (the pull-ahead
        half-RPC); returns the in-flight message, or None on shutdown."""
        msg = GradMsg(self.wid, grad,
                      self._view if (self.telemetry and grad is not None)
                      else None,
                      self._view_step, t_send, seq=seq)
        if not self.mailbox.put(msg, self.stop):
            return None
        if trace.enabled:
            trace.instant("worker.rpc_post", "worker", worker=self.wid,
                          seq=seq)
        return msg

    def _await(self, msg: GradMsg) -> bool:
        """Settle one in-flight push: wait for its reply and adopt the
        fresher view."""
        tr = trace.enabled
        if tr:
            trace.begin("worker.rpc_await", "worker", worker=self.wid,
                        seq=msg.seq)
        reply = msg.wait_reply(self.rpc_timeout)
        if tr:
            if reply is None:
                trace.end()
            else:
                trace.end(step=reply.step)
        if reply is None:
            return False
        self._view, self._view_step = reply.view, reply.step
        if msg.grad is not None:
            self.grads_sent += 1
        return True

    def _drain_pending(self) -> bool:
        ok = True
        while self._pending:
            ok = self._await(self._pending.popleft()) and ok
        return ok

    # -- one RPC ---------------------------------------------------------
    def _push(self, grad, t_send: float, seq: int = -1) -> bool:
        """One fused push-pull RPC; ``seq`` is the gradient's number
        (-1 for a pull-only request)."""
        msg = GradMsg(self.wid, grad,
                      self._view if (self.telemetry and grad is not None)
                      else None,
                      self._view_step, t_send,
                      rows=self.hot_rows if grad is None else None,
                      seq=seq)
        # the fused push-pull round trip: enqueue + queueing delay +
        # master service time, as seen from this worker, up to the
        # reply in hand (its step is the gradient's apply step)
        tr = trace.enabled
        if tr:
            trace.begin("worker.rpc", "worker", worker=self.wid, seq=seq)
        if not self.mailbox.put(msg, self.stop):
            if tr:
                trace.end()
            return False
        reply = msg.wait_reply(self.rpc_timeout)
        if tr:
            if reply is None:
                trace.end()
            else:
                trace.end(step=reply.step)
        if reply is None:
            return False
        if reply.rows is not None:
            # partial (hot-row) view: patch the declared rows into the
            # cached copy instead of replacing it
            self._view = self.merge_view(self._view, reply.view)
            self._view_step = reply.step
        else:
            self._view, self._view_step = reply.view, reply.step
        if grad is not None:
            self.grads_sent += 1
        return True

    def _grad(self, seq: int):
        """Gradient ``seq`` of this worker: the call into the feed, then
        the call that dispatches the backward->wire program (batch
        transfer and any blocking by the runtime included)."""
        tr = trace.enabled
        if tr:
            trace.begin("worker.next_batch", "worker", worker=self.wid,
                        seq=seq)
        batch = self.next_batch(self.wid, seq)
        if tr:
            trace.end()
            trace.begin("worker.grad", "worker", worker=self.wid, seq=seq)
        grad = self.grad_jit(self._view, batch)
        if tr:
            trace.end()
        return grad

    # -- deterministic mode ---------------------------------------------
    def _run_deterministic(self):
        counter = 0
        while True:
            t = self.clock.acquire(self.wid)
            if t is None:
                return
            ok = False
            try:
                if (not self.stop.is_set()
                        and self.master.applied < self.master.total):
                    grad = self._grad(counter)
                    ok = self._push(grad, t, counter)
                    counter += 1
            finally:
                if ok:
                    stall = (self.injector.stall(self.wid)
                             if self.injector is not None else 0.0)
                    self.clock.release(self.wid, extra=stall)
                else:
                    self.clock.withdraw(self.wid)
            if not ok:
                return

    # -- paced / free modes ----------------------------------------------
    def _run_live(self):
        try:
            self._live_loop()
        except BaseException:
            # settle best-effort, but a secondary drain failure (e.g.
            # wait_reply timing out against an already-wedged master)
            # must not replace the loop's own error in worker.error
            try:
                self._drain_pending()
            except BaseException:  # noqa: BLE001 - root cause wins
                self._pending.clear()
            raise
        else:
            # settle any still-in-flight pull-ahead pushes so applied
            # grads are counted (end-of-run rejections resolve to None
            # and the master's shutdown path unblocks stragglers)
            self._drain_pending()

    def _live_loop(self):
        counter = 0
        while (not self.stop.is_set()
               and self.master.applied < self.master.total):
            stall = 0.0
            if self.injector is not None:
                back = self.injector.offline_until(self.wid,
                                                   self.master.step)
                if back is not None:
                    if trace.enabled:
                        trace.instant("faults.dropout", "faults",
                                      worker=self.wid, back_step=back)
                    # an offline worker abandons its pipeline first: the
                    # in-flight pushes settle, then the stale view is
                    # discarded by the rejoin pull
                    self._drain_pending()
                    if not self._await_rejoin(back):
                        return
                    if trace.enabled:
                        trace.instant("faults.rejoin", "faults",
                                      worker=self.wid)
                    # rejoin: stale view discarded, pull-only request
                    if not self._push(None, self.now_fn()):
                        return
                    continue
                stall = self.injector.stall(self.wid)
            dt = stall + (self.draw(self.wid) if self.mode == "paced"
                          else 0.0)
            if dt > 0.0 and self.stop.wait(dt * self.time_scale):
                return
            if self.gate is not None and not self.gate.acquire(self.wid):
                return
            try:
                seq = counter
                counter += 1
                grad = self._grad(seq)
                if self.pipeline_depth == 0:
                    ok = self._push(grad, self.now_fn(), seq)
                else:
                    # pull-ahead: post now, settle the OLDEST in-flight
                    # push only once more than `depth` are outstanding —
                    # the RPC round trip hides behind the next gradient
                    msg = self._post(grad, self.now_fn(), seq)
                    ok = msg is not None
                    if ok:
                        self._pending.append(msg)
            finally:
                if self.gate is not None:
                    self.gate.advance()
            while ok and len(self._pending) > self.pipeline_depth:
                ok = self._await(self._pending.popleft())
            if not ok:
                return

    def _await_rejoin(self, back_step: int) -> bool:
        while not self.stop.is_set() and self.master.step < back_step:
            if self.master.applied >= self.master.total:
                return False
            self.stop.wait(0.002)
        return not self.stop.is_set()
