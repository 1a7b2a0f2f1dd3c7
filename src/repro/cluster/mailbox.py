"""Bounded gradient mailbox between worker threads and the master.

The mailbox is the cluster's only synchronization point on the hot path:
workers ``put`` gradient messages (blocking when the queue is full — the
back-pressure a real parameter server applies to fast workers), and the
master ``drain``s up to k messages at a time for a coalesced receive.

Each message doubles as its own reply slot: the push is a fused push-pull
RPC — the master answers with the post-update parameter view, exactly the
``receive`` -> ``send`` sequence of the discrete-event engine.  Because
the reply slot travels WITH the message, worker pull-ahead
(``ClusterConfig.pipeline_depth``) needs no protocol change: a worker
keeps up to ``depth`` pushes in flight simply by deferring
``wait_reply`` on their messages while it computes the next gradient.

For the row-sharded multi-master (``repro.cluster.sharded``) the same
protocol fans out: ``FanoutMailbox`` splits one worker message into S
``ShardMsg`` parts (each carrying only that shard's row slice of the
gradient/view) and a ``_ReplyGroup`` reassembles the S shard replies into
the single ``Reply`` the worker is waiting on — the worker pushes a
gradient ONCE and never knows the master is sharded.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any

from ..obs import trace


@dataclasses.dataclass
class Reply:
    """Master's answer to one gradient push: the fresh parameter view and
    the master step it was issued at (the worker's next ``pull_step``).

    ``rows`` is None for a full view; a hot-row pull answered over only
    the requested row range carries that ``(r0, r1)`` back so the worker
    merges the partial view instead of replacing its copy."""
    view: Any
    step: int
    rows: Any = None


class GradMsg:
    """One worker->master message.

    ``grad is None`` marks a pull-only request (a rejoining worker asking
    for fresh parameters without contributing an update).  ``rows``
    (pull-only) is an optional ``(r0, r1)`` flat-row range the worker
    declares hot: the master may serve the view over just those rows
    (``Reply.rows`` echoes the range it honored; sent-snapshot masters
    fall back to the full view and leave it None).

    ``(worker_id, seq)`` identifies the gradient: ``seq`` is the
    worker's own gradient number, carried by every trace span of that
    gradient on both sides of the mailbox.
    """

    __slots__ = ("worker_id", "grad", "view", "view_step", "t_send",
                 "rows", "seq", "_event", "_reply")

    def __init__(self, worker_id: int, grad: Any, view: Any,
                 view_step: int, t_send: float, rows=None, seq: int = -1):
        self.worker_id = worker_id
        self.grad = grad
        self.view = view              # params the gradient was computed on
        self.view_step = view_step    # master step the view was issued at
        self.t_send = t_send          # virtual (det/paced) or wall time
        self.rows = rows              # hot-row range for pull-only requests
        self.seq = seq                # the worker's gradient number
        self._event = threading.Event()
        self._reply: Reply | None = None

    # -- reply slot ------------------------------------------------------
    def respond(self, reply: Reply | None):
        self._reply = reply
        self._event.set()

    def wait_reply(self, timeout: float | None = None) -> Reply | None:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"worker {self.worker_id}: no master reply in {timeout}s")
        return self._reply


class _ReplyGroup:
    """Reassembles S shard replies into one worker-facing ``Reply``.

    The worker's view is the range-ordered tuple of shard view slices;
    the reply step is shard 0's (every shard applies every message, so
    the counters only diverge transiently in live modes — shard 0 is the
    canonical clock).  Any shard replying ``None`` (shutdown / overflow)
    fails the whole group.  Telemetry partial sums (per-shard ``sum d^2``
    / ``sum g^2`` over the shard's rows) accumulate here and flush to the
    owner's callback once every shard has applied the message.
    """

    __slots__ = ("parent", "shards", "_lock", "_views", "_left", "_failed",
                 "_step0", "_rows_ok", "_tele_cb", "_drop_cb", "_tele_left",
                 "_tele_closed", "_d2", "_g2", "_meta")

    def __init__(self, parent: GradMsg, shards: int, tele_cb=None,
                 drop_cb=None):
        self.parent = parent
        self.shards = shards
        self._lock = threading.Lock()
        self._views = [None] * shards
        self._left = shards
        self._failed = False
        self._step0 = 0
        self._rows_ok = True         # every shard honored its hot-row slice
        self._tele_cb = tele_cb
        self._drop_cb = drop_cb
        self._tele_left = shards
        self._tele_closed = False
        self._d2 = 0.0
        self._g2 = 0.0
        self._meta = None            # (worker, step, lag, t) from shard 0

    def shard_reply(self, sid: int, reply: Reply | None):
        with self._lock:
            if reply is None:
                self._failed = True
            else:
                self._views[sid] = reply.view
                if reply.rows is None:
                    self._rows_ok = False
                if sid == 0:
                    self._step0 = reply.step
            self._left -= 1
            done = self._left == 0
            failed = self._failed
        if done:
            # the assembled reply is partial (hot rows) only when the
            # parent asked for a range AND every shard served its slice
            # (a sent-snapshot master falls back to full shard views)
            rows = (self.parent.rows
                    if self.parent.rows is not None and self._rows_ok
                    else None)
            self.parent.respond(None if failed else
                                Reply(view=tuple(self._views),
                                      step=self._step0, rows=rows))
            # the group is finished: shards that applied the message have
            # already contributed their telemetry (apply precedes reply),
            # shards that rejected it never will — settle the partials now
            self._close_telemetry()

    def add_telemetry(self, sid: int, *, worker: int, step: int, lag: int,
                      t: float, d2: float, g2: float):
        with self._lock:
            self._d2 += d2
            self._g2 += g2
            if sid == 0:
                self._meta = (worker, step, lag, t)
            self._tele_left -= 1
            done = self._tele_left == 0
        if done:
            self._close_telemetry()

    def _close_telemetry(self):
        """Flush the accumulated partials (every shard contributed and
        shard 0's meta landed) or count the drop (the group finished with
        partials that can never complete — a shard rejected the message,
        or shard 0 never applied it).  Fires exactly once; groups with no
        partials at all (pulls, telemetry-off runs) are not drops."""
        with self._lock:
            if self._tele_closed:
                return
            self._tele_closed = True
            complete = self._tele_left == 0 and self._meta is not None
            started = self._tele_left < self.shards
            meta, d2, g2 = self._meta, self._d2, self._g2
        if complete:
            if self._tele_cb is not None:
                worker, step, lag, t = meta
                self._tele_cb(worker=worker, step=step, lag=lag, t=t,
                              d2=d2, g2=g2)
        elif started and self._drop_cb is not None:
            self._drop_cb()


class ShardMsg(GradMsg):
    """One shard's slice of a fanned-out worker message.  Responding
    feeds the shared ``_ReplyGroup``; the worker blocks on the parent."""

    __slots__ = ("group", "sid")

    def __init__(self, worker_id: int, grad: Any, view: Any,
                 view_step: int, t_send: float, *, group: _ReplyGroup,
                 sid: int, rows=None):
        super().__init__(worker_id, grad, view, view_step, t_send,
                         rows=rows)
        self.group = group
        self.sid = sid

    def respond(self, reply: Reply | None):
        super().respond(reply)
        self.group.shard_reply(self.sid, reply)


class FanoutMailbox:
    """Worker-facing front of the sharded master: ``put`` fans one
    message out to the S per-shard mailboxes.  Gradients and telemetry
    views arrive as range-ordered tuples of row slices (the worker's grad
    jit scatters on its pack path), so shard s simply takes element s —
    no slicing on the master side.

    The fan-out is ATOMIC (one lock across the S enqueues): every shard
    sees the identical arrival order, so the first ``total`` gradient
    messages — the set each shard applies before end-of-run truncation —
    is the same on every shard.  Without it, two workers' fan-outs can
    interleave differently per shard and the shards would apply
    *different* message sets at the total boundary.  The lock covers
    only queue appends (a blocked bounded ``Mailbox.put`` drains
    independently of other workers' puts, so it cannot deadlock).

    ``ranges`` (the shards' static row ranges) lets a pull-only hot-row
    request fan out sliced: each part asks its shard for the local-row
    intersection of the worker's hot range with the shard's range (empty
    intersections become zero-row requests the shard answers with a
    zero-row view).  ``full_fanout=True`` is the row-rebalancing wire
    mode: shard ranges move at run time, so every part carries the WHOLE
    packed gradient and each shard slices its own (current) rows inside
    its fused jit — hot-row slicing is disabled there (ranges are no
    longer static)."""

    def __init__(self, mailboxes: list["Mailbox"], tele_cb=None,
                 ranges=None, full_fanout: bool = False, drop_cb=None):
        self.mailboxes = list(mailboxes)
        self._tele_cb = tele_cb
        self._drop_cb = drop_cb
        self._lock = threading.Lock()
        self.ranges = (None if full_fanout or ranges is None
                       else tuple(ranges))
        self.full_fanout = full_fanout

    @property
    def depth(self) -> int:
        """Deepest per-shard queue — a lock-free sampler read (see
        ``Mailbox.depth``)."""
        return max(mb.depth for mb in self.mailboxes)

    def __len__(self) -> int:
        return self.depth

    def put(self, msg: GradMsg, stop) -> bool:
        shards = len(self.mailboxes)
        group = _ReplyGroup(msg, shards, tele_cb=self._tele_cb,
                            drop_cb=self._drop_cb)
        if self.full_fanout:
            # rebalance wire mode: one full packed gradient, shared by
            # every part (read-only on the shards; each slices in-jit)
            parts = [
                ShardMsg(msg.worker_id, msg.grad, msg.view, msg.view_step,
                         msg.t_send, group=group, sid=s)
                for s in range(shards)
            ]
        else:
            part_rows = [None] * shards
            if msg.rows is not None and self.ranges is not None:
                h0, h1 = msg.rows
                part_rows = [
                    (max(h0, s0) - s0, max(min(h1, s1), max(h0, s0)) - s0)
                    for s0, s1 in self.ranges
                ]
            parts = [
                ShardMsg(msg.worker_id,
                         None if msg.grad is None else msg.grad[s],
                         None if msg.view is None else msg.view[s],
                         msg.view_step, msg.t_send, group=group, sid=s,
                         rows=part_rows[s])
                for s in range(shards)
            ]
        with self._lock:
            for s, (part, mb) in enumerate(zip(parts, self.mailboxes)):
                if not mb.put(part, stop):
                    # shutdown mid-fanout: shards 0..s-1 already hold
                    # their parts (their servers / reject_pending will
                    # answer); fail the rest so the group can complete
                    for rest in parts[s:]:
                        rest.respond(None)
                    return False
        return True


class Mailbox:
    """Bounded FIFO with batched (coalescing) drain.

    Queue depth is mirrored into ``_depth``, a plain int updated only
    while the condition lock is already held for the queue mutation
    itself.  ``depth`` reads it WITHOUT the lock (int loads are atomic
    under the GIL), so the observability sampler — which polls depth at
    a few hundred Hz — never contends with the worker put / master drain
    hot path.  The reading is an instantaneous snapshot, exactly what a
    depth sample wants.
    """

    def __init__(self, capacity: int = 0):
        self._capacity = capacity          # 0 = unbounded
        self._q: collections.deque[GradMsg] = collections.deque()
        self._cond = threading.Condition()
        self._depth = 0                    # lock-free depth mirror

    @property
    def depth(self) -> int:
        """Current queue depth — lock-free, for sampler threads."""
        return self._depth

    def __len__(self) -> int:
        return self._depth

    def put(self, msg: GradMsg, stop: threading.Event) -> bool:
        """Enqueue; blocks while full.  Returns False if the cluster shut
        down before the message could be enqueued."""
        with self._cond:
            while self._capacity and len(self._q) >= self._capacity:
                if stop.is_set():
                    return False
                self._cond.wait(timeout=0.05)
            if stop.is_set():
                return False
            self._q.append(msg)
            self._depth = len(self._q)
            self._cond.notify_all()
        return True

    def drain(self, max_k: int, stop: threading.Event,
              timeout: float = 0.05, pow2: bool = False) -> list[GradMsg]:
        """Pop up to ``max_k`` queued messages (the coalesced receive
        window).  Blocks until at least one message is available or the
        stop flag is raised; never waits for the window to fill — when the
        queue is shallow the master degrades gracefully to k=1.

        ``pow2`` rounds the batch size down to a power of two so the
        master's fused receive compiles O(log k) variants instead of one
        per batch size (at steady state the queue is deep and the batch is
        exactly ``max_k`` anyway)."""
        # the span is mostly WAIT time: in Perfetto, long drain spans
        # against short apply spans = an under-fed (idle) server
        tr = trace.enabled
        if tr:
            trace.begin("mailbox.drain", "mailbox")
        with self._cond:
            while not self._q:
                if stop.is_set():
                    if tr:
                        trace.end(k=0)
                    return []
                self._cond.wait(timeout=timeout)
            k = min(max_k, len(self._q))
            if pow2:
                k = 1 << (k.bit_length() - 1)
            out = [self._q.popleft() for _ in range(k)]
            self._depth = len(self._q)
            self._cond.notify_all()
        if tr:
            trace.end(k=k)
        return out

    def drain_nowait(self) -> list[GradMsg]:
        with self._cond:
            out = list(self._q)
            self._q.clear()
            self._depth = 0
            self._cond.notify_all()
            return out
