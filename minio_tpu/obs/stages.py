"""Request X-ray — per-stage latency attribution (the diagnosis half
of the obs plane the PR-2..4 trace/stats work could not answer).

``mc admin trace`` and the last-minute p50/p99 families say *what* is
slow; this module says *why*: every S3 request carries a
:class:`StageClock` (a contextvar, minted in ``_dispatch`` beside the
request ID) and the instrumented layers charge their wall time to
named stages as the request crosses them:

  ``admission``      request-pool semaphore wait (cmd/handler-api.go
                     maxClients analog)
  ``auth``           SigV4/SigV2 verification incl. aws-chunked
                     signature checking
  ``policy``         authorization: bucket policy + IAM + the external
                     OPA webhook when configured
  ``body_read``      reading the request body off the socket
  ``lock_wait``      namespace-lock acquisition (local or dsync)
  ``meta_read``      the quorum metadata read: the ``read_version``
                     fan-out over the set's drives + the pick of the
                     quorum FileInfo (HEAD, GET, a cache hit's
                     validation; exclusive, so inside ``cache`` it
                     comes out of ``cache``'s self time)
  ``memgov``         memory-governor admission accounting
  ``cache``          hot-read plane serve (hit validation included)
  ``encode``         erasure encode + bitrot framing (PUT)
  ``md5``            the ETag md5, wherever it runs (async detail
                     always: on the pool it overlaps ``encode`` and
                     ``drive_commit``; on the request thread it is
                     also inside a serial stage or ``other``)
  ``decode``         shard assembly / erasure decode (GET)
  ``batch_wait``     cross-request codec batcher queue wait
  ``drive_read``     shard-segment fan-out wall time (GET)
  ``drive_commit``   commit fan-out wall time (PUT; a DELETE's
                     ``delete_version`` fan-out under its own API label)
  ``write_enqueue``  writer-plane enqueue stalls (pipelined PUT)
  ``write_drain``    writer-plane drain wait (pipelined PUT)
  ``stream_wait``    the request thread pulling the next chunk of a
                     streamed GET body: with readahead, its wait for
                     the producer thread; without, the producer's own
                     stages nest inside it and take their time out
  ``body_write``     writing the response body to the socket
  ``rpc``            internode RPC legs (async detail — overlaps the
                     request thread by design)
  ``other``          the unattributed remainder, computed at finish

Stages recorded on the clock's OWNER thread (the request handler) are
*serial* and exclusive: the clock keeps a stack, a nested stage's time
is subtracted from its parent, so the serial stage vector plus
``other`` reconciles with the measured request total exactly (the
reconciliation contract tests/test_xray.py pins).  The same ``stage``
/ ``add`` sites called from a pool, writer, or readahead thread (the
clock rides into them next to the request ID) route automatically to
the *async detail* vector — attributed but deliberately outside the
serial sum, because overlapping wall intervals cannot both be part of
one request's wall clock.

Idle/always-on contract (the PR-2 discipline): with no clock armed
every instrumented site pays one contextvar read and a None check.
With a clock armed the cost is monotonic reads plus in-place updates
of two small per-request dicts — no per-event allocation, bounded by
the stage-name catalog however many batches a huge PUT streams.
``ENABLED`` exists for the ``bench.py xray`` A/B leg and test
isolation; production always runs armed.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from typing import Optional

# the full stage catalog — every name the instrumented sites may emit.
# The analysis docs-drift rule (obs-docs-drift) checks each appears in
# docs/observability.md; the xray tests check emitted names stay inside
# this set.
STAGE_NAMES = (
    "admission", "auth", "policy", "body_read", "lock_wait", "meta_read",
    "memgov", "cache", "encode", "md5", "decode", "batch_wait",
    "drive_read", "drive_commit", "write_enqueue", "write_drain",
    "stream_wait", "body_write", "rpc", "other",
)

# bench A/B switch (MT_XRAY_DISABLE=1 runs the hot paths with the
# clock never armed — the overhead-measurement baseline)
ENABLED = os.environ.get("MT_XRAY_DISABLE", "") not in ("1", "true")

_CLOCK: contextvars.ContextVar[Optional["StageClock"]] = \
    contextvars.ContextVar("mt_stage_clock", default=None)


class StageClock:
    """One request's stage accumulator.

    The OWNER thread (whoever constructed the clock) records serial
    stages through :meth:`push`/:meth:`pop`; nesting is handled with a
    stack so recorded times are exclusive self-times summing to at
    most the request wall time.  Any other thread holding the clock
    lands in ``async_detail`` — plain in-place dict adds whose rare
    cross-thread races could only under-count attribution detail,
    never corrupt the serial reconciliation.
    """

    __slots__ = ("t0_ns", "owner", "_stack", "serial", "async_detail",
                 "gatings")

    def __init__(self):
        self.t0_ns = time.monotonic_ns()
        self.owner = threading.get_ident()
        # stack entries: [name, start_ns, child_ns]
        self._stack: list = []
        self.serial: dict = {}
        self.async_detail: dict = {}
        # quorum critical-path rows (obs/critpath.py): compact tuples
        # (plane, k, n, gating_label, kth_label, kth_ns, wall_ns,
        # trail_ns), appended at each quorum reduction the request
        # crossed and rendered into its flight-recorder row — a list
        # append per reduction, no dicts on the hot path
        self.gatings: list = []

    # -- serial stages (owner thread only) -----------------------------------

    def push(self, name: str) -> None:
        self._stack.append([name, time.monotonic_ns(), 0])

    def pop(self) -> None:
        name, start, child = self._stack.pop()
        dur = time.monotonic_ns() - start
        if self._stack:
            self._stack[-1][2] += dur
        self_ns = dur - child
        if self_ns > 0:
            self.serial[name] = self.serial.get(name, 0) + self_ns

    def add(self, name: str, dur_ns: int) -> None:
        """Record an already-measured interval: serial on the owner
        thread (charged against the enclosing stage so nothing double
        counts), async detail from anywhere else."""
        if threading.get_ident() != self.owner:
            self.add_async(name, dur_ns)
            return
        if self._stack:
            self._stack[-1][2] += dur_ns
        self.serial[name] = self.serial.get(name, 0) + dur_ns

    # -- async detail (any thread) -------------------------------------------

    def add_async(self, name: str, dur_ns: int) -> None:
        d = self.async_detail
        d[name] = d.get(name, 0) + dur_ns

    # -- finish ---------------------------------------------------------------

    def finish(self, total_ns: int | None = None
               ) -> tuple[dict, dict, int]:
        """Close out: returns ``(serial, async, unattributed)`` where
        ``serial`` maps stage -> ns with ``other`` = total -
        sum(serial) (clamped at 0) appended, so the serial stages plus
        ``other`` reconcile with the total exactly; ``async`` is the
        parallel detail; ``unattributed`` is the raw remainder before
        clamping (negative would mean a double-count — the
        reconciliation tests assert it never is)."""
        while self._stack:              # abandoned mid-stage (error path)
            self.pop()
        if total_ns is None:
            total_ns = time.monotonic_ns() - self.t0_ns
        serial = dict(self.serial)
        unattributed = total_ns - sum(serial.values())
        serial["other"] = max(0, unattributed)
        return serial, dict(self.async_detail), unattributed


# -- module-level plumbing ----------------------------------------------------

def begin() -> Optional[StageClock]:
    """Mint + arm a clock for the current context (the S3 dispatcher);
    returns None when the plane is disabled (bench baseline)."""
    if not ENABLED:
        return None
    clock = StageClock()
    _CLOCK.set(clock)
    return clock


def clear() -> None:
    _CLOCK.set(None)


def current() -> Optional[StageClock]:
    return _CLOCK.get()


def set_clock(clock: Optional[StageClock]) -> None:
    """Explicit propagation into pool/writer/readahead threads
    (contextvars do not cross thread boundaries) — the request-ID
    discipline from obs/trace.py.  Non-owner threads route to async
    detail automatically."""
    _CLOCK.set(clock)


class _Stage:
    """Tiny reusable context manager: ``with stage("auth"): ...`` —
    one contextvar read and a None check when no clock is armed; on a
    non-owner thread the interval lands in async detail."""

    __slots__ = ("name", "_clock", "_serial", "_t0")

    def __init__(self, name: str):
        self.name = name
        self._clock = None
        self._serial = False
        self._t0 = 0

    def __enter__(self):
        c = _CLOCK.get()
        self._clock = c
        if c is not None:
            if threading.get_ident() == c.owner:
                self._serial = True
                c.push(self.name)
            else:
                self._serial = False
                self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        c = self._clock
        self._clock = None
        if c is not None:
            if self._serial:
                c.pop()
            else:
                c.add_async(self.name,
                            time.monotonic_ns() - self._t0)
        return False


def stage(name: str) -> _Stage:
    return _Stage(name)


def add(name: str, dur_ns: int) -> None:
    """Add an already-measured interval against the armed clock, if
    any (owner thread -> serial, others -> async detail)."""
    c = _CLOCK.get()
    if c is not None:
        c.add(name, dur_ns)


def add_async(name: str, dur_ns: int) -> None:
    """Async-detail add against the armed clock, if any."""
    c = _CLOCK.get()
    if c is not None:
        c.add_async(name, dur_ns)


def note_gating(row: tuple) -> None:
    """Attach one quorum critical-path row to the armed clock, if any
    (list append under the GIL — safe from writer/pool threads the
    clock rode into, same discipline as add_async)."""
    c = _CLOCK.get()
    if c is not None:
        c.gatings.append(row)
