"""Continuous template batching (round 21): the per-template rendezvous that
fuses N in-flight executions of ONE plan template into a single batched
device program.

The shape is the LLM-serving continuous-batching loop re-planned for SQL
templates: requests for the same compiled program but different bindings
coalesce into one dispatch (the per-REQUEST analog of the round-6 per-split
``_coalesced_batches``).  Each template-cache key owns a LANE:

- the FIRST request on an idle lane is the LEADER — it runs the exact
  existing single-statement path immediately, so an empty window adds ZERO
  latency or extra work (the budget suite's single-statement ceilings are
  untouched by construction);
- requests arriving while the lane is busy QUEUE; when the leader finishes
  it hands the lane to the first queued member, which becomes the DRIVER:
  it sleeps the gather window (``DEFAULT_WINDOW_MS``), drains up to
  ``DEFAULT_MAX_BATCH`` members, and runs ONE fused execution
  (LocalExecutor.execute_batched) whose per-lane results resolve every
  member;
- a whole-batch failure (BatchUnsupported, a device fault) re-runs EVERY
  member on its own serial path — no member ever inherits another's error,
  and a per-lane decode error fails only its own request.

The batcher is pure host-side thread choreography: zero _jit/_host traffic
of its own (the fused execution accounts its spend on the driver's
statement like any executed plan).  What a member spends queued on a busy
lane, and the driver in its gather window, is the statement's
``batcher.wait`` span and ``batch_wait_s`` counter."""

from __future__ import annotations

import threading
import time

from . import tracing

__all__ = ["TemplateBatcher"]


# test seam: when set, called with the lane key by a LEADER after its own
# serial execution completes and BEFORE it hands the lane to a queued
# driver — tests park the leader here to deterministically accumulate a
# multi-member window instead of racing the wall clock
LEADER_EXIT_HOOK = None


DEFAULT_WINDOW_MS = 2.0  # a driver's gather window
DEFAULT_MAX_BATCH = 16  # members one fused execution takes


class _Member:
    __slots__ = ("runtime", "event", "drive", "serial", "result", "error",
                 "batched_with")

    def __init__(self, runtime):
        self.runtime = runtime
        self.event = threading.Event()
        self.drive = False  # woken to DRIVE the next window
        self.serial = False  # woken to fall back to its own serial run
        self.result = None
        self.error = None
        self.batched_with = 0


class _Lane:
    __slots__ = ("busy", "queue")

    def __init__(self):
        self.busy = False
        self.queue: list = []


class TemplateBatcher:
    """Per-template-key execution lanes (see module docstring).

    ``execute`` is the only entry point; ``info()`` snapshots the metrics
    surface (/v1/metrics template-batch counters + size histogram)."""

    def __init__(self, window_ms=None, max_batch=None, enabled=True):
        self.window_s = (DEFAULT_WINDOW_MS if window_ms is None
                         else float(window_ms)) / 1000.0
        self.max_batch = max(DEFAULT_MAX_BATCH if max_batch is None
                             else int(max_batch), 1)
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._lanes: dict = {}
        self.batches_total = 0
        self.batched_requests_total = 0
        self._size_hist: dict = {}  # fused batch size -> count

    def execute(self, key, runtime, serial_fn, batch_fn):
        """Run one template request through the lane for ``key``.

        ``serial_fn(runtime) -> result`` is the exact single-statement path;
        ``batch_fn(runtimes) -> [result|Exception, ...]`` the fused one.
        Returns ``(result, batched_with)``: ``batched_with == 0`` means the
        request executed serially (idle-lane leader, singleton window, or
        fallback); > 0 is the fused batch size that served it.  Raises the
        member's OWN error only."""
        if not self.enabled:
            return serial_fn(runtime), 0
        with self._lock:
            lane = self._lanes.setdefault(key, _Lane())
            member = None
            if lane.busy:
                member = _Member(runtime)
                lane.queue.append(member)
            else:
                lane.busy = True
        if member is None:
            # leader on an idle lane: the unmodified serial path, now
            try:
                return serial_fn(runtime), 0
            finally:
                hook = LEADER_EXIT_HOOK
                if hook is not None:
                    try:
                        hook(key)
                    except Exception:
                        pass
                self._handoff(lane)
        # the lane's wait state: joined a busy lane -> woken (to drive, to run
        # serially, or resolved by the driver's fused run)
        with tracing.wait_span("batcher.wait", "batch_wait_s"):
            member.event.wait()
        if member.drive:
            return self._drive(lane, member, serial_fn, batch_fn)
        if member.serial:
            # the window's fused run failed as a whole: run our own serial
            return serial_fn(member.runtime), 0
        if member.error is not None:
            raise member.error
        return member.result, member.batched_with

    def _drive(self, lane, member, serial_fn, batch_fn):
        """First queued member after a handoff: gather a window, run the
        fused batch, resolve every member, hand the lane on."""
        if self.window_s > 0:
            with tracing.wait_span("batcher.wait", "batch_wait_s",
                                   phase="gather"):
                time.sleep(self.window_s)
        with self._lock:
            take = lane.queue[:self.max_batch - 1]
            del lane.queue[:len(take)]
        group = [member] + take
        if len(group) == 1:
            # nobody joined the window: the serial path is strictly better
            # (already compiled, no lane padding)
            try:
                return serial_fn(member.runtime), 0
            finally:
                self._handoff(lane)
        try:
            results = batch_fn([m.runtime for m in group])
            if not isinstance(results, (list, tuple)) \
                    or len(results) != len(group):
                raise RuntimeError(
                    "batch executor returned %r results for %d members"
                    % (None if results is None else len(results),
                       len(group)))
        except BaseException as e:
            # whole-batch failure: every OTHER member re-runs serially on
            # its own thread; this thread does the same (after freeing
            # them), unless the interpreter itself is going down
            for m in group[1:]:
                m.serial = True
                m.event.set()
            self._handoff(lane)
            if isinstance(e, (KeyboardInterrupt, SystemExit, GeneratorExit)):
                raise
            return serial_fn(member.runtime), 0
        n = len(group)
        with self._lock:
            self.batches_total += 1
            self.batched_requests_total += n
            self._size_hist[n] = self._size_hist.get(n, 0) + 1
        for m, r in zip(group, results):
            m.batched_with = n
            if isinstance(r, BaseException):
                m.error = r
            else:
                m.result = r
        for m in group[1:]:
            m.event.set()
        self._handoff(lane)
        if member.error is not None:
            raise member.error
        return member.result, member.batched_with

    def _handoff(self, lane) -> None:
        """Release the lane: promote the first queued member to driver, or
        mark the lane idle.  Every exit path of a lane holder runs this —
        a queued member can never be stranded."""
        with self._lock:
            if lane.queue:
                nxt = lane.queue.pop(0)
                nxt.drive = True
                nxt.event.set()
            else:
                lane.busy = False

    def info(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "window_ms": self.window_s * 1000.0,
                    "max_batch": self.max_batch,
                    "batches_total": self.batches_total,
                    "batched_requests_total": self.batched_requests_total,
                    "sizes": dict(self._size_hist)}
