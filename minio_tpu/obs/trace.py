"""Request tracing (pkg/trace/trace.go:26-40, cmd/http-tracer.go:164).

Every S3/admin request is summarised as a ``trace.Info``-shaped dict and
published to the global :data:`HTTP_TRACE` pub/sub.  ``mc admin trace``
equivalents subscribe via the admin ``trace`` route and stream JSON lines;
on a cluster the admin node aggregates peer streams over the internode RPC
(peerRESTMethodTrace, cmd/peer-rest-common.go:54).

Beyond the HTTP frontend, the deep-tracing plane publishes SUBSYSTEM
spans to the same hub (``mc admin trace -a`` analog, trace types per
pkg/trace.Type):

  ``storage``    per-drive-call spans (storage/xl_storage.py + remote.py)
  ``internode``  RPC client/server spans (parallel/rpc.py)
  ``tpu``        the device codec path, ``<op>.<leg>``: whole
                 dispatches (``encode.dispatch``, ``hash.dispatch``,
                 ``encode-bitrot.batch`` ...) with shard geometry and bytes,
                 and their legs (prep/upload/launch/fetch/frame), all
                 through :class:`span` (ops/codec.py + friends)
  ``read``       the read side's legs, ``<plane>.<leg>``: the quorum
                 metadata read (``meta.fanout``, ``meta.pick``) and a
                 GET's shard read (``get.fanout``, ``get.verify``,
                 ``get.assemble``, ``get.copy_out``), through
                 :class:`span` (objectlayer/erasure_object.py)
  ``scanner``    data-crawler per-bucket spans (background/crawler.py)
  ``healing``    heal-sweep / MRF per-object spans (background/heal.py)
  ``replication``  per-object replication spans
                 (background/replication.py)

Every span carries the originating request ID (Dapper-style correlation,
Sigelman et al. 2010): the S3 frontend mints one per request into a
contextvar; internode RPC forwards it in an ``X-Request-ID`` header so
spans emitted on a *peer* node still name the frontend request.

Publishing is skipped entirely when nobody is subscribed, mirroring the
reference's ``globalHTTPTrace.NumSubscribers() > 0`` guard — the hot
path pays a single predicate (:func:`active`), no dict construction.

:class:`span` is the one way a leg of the device codec path or of the
read side is timed: one enter/exit feeds the always-on span ring, the
type's leg histogram (``LEG_FAMILIES``: wall; one span in
``CPU_SAMPLE_EVERY`` also its thread's CPU time, in the ``_cpu`` twin)
and — when ops/device.py has installed an annotator — the profiler's own
trace, so the program's spans sit on the device trace's clock.  This
package never imports JAX.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading
import time
from typing import Any, Dict

from ..admin.metrics import GLOBAL as _metrics
from ..admin.metrics import KERNEL_BUCKETS
from ..utils.pubsub import PubSub

# global trace hub (reference: globalHTTPTrace)
HTTP_TRACE = PubSub(max_queue=4000)

# subsystem trace types (pkg/trace.Type); "http" stays the default so
# existing `admin trace` consumers see no change without ?type=.
# scanner/healing/replication are the background planes (pkg/trace
# TraceScanner/TraceHealing/TraceReplication) — per-object spans from
# the autonomous loops, same zero-subscriber idle contract as the rest.
TRACE_TYPES = ("http", "storage", "internode", "tpu", "read",
               "scanner", "healing", "replication", "watchdog")

# headers never to leak into traces (cmd/http-tracer.go redacts these;
# the reference strips ALL SSE-C key material — including the key MD5 —
# and browser cookies)
_REDACTED_HEADERS = {"authorization", "x-amz-security-token",
                     "cookie", "set-cookie",
                     "x-amz-server-side-encryption-customer-key",
                     "x-amz-server-side-encryption-customer-key-md5",
                     "x-amz-copy-source-server-side-encryption-customer-key",
                     "x-amz-copy-source-server-side-encryption-customer"
                     "-key-md5"}

# the request ID minted at the S3 frontend, visible to every subsystem
# call made on behalf of that request (threads started per-request see
# it via explicit propagation: erasure fan-out and RPC header)
_REQUEST_ID: contextvars.ContextVar[str] = contextvars.ContextVar(
    "mt_request_id", default="")

# this process's node name for span attribution (set once at server
# boot; cluster nodes use their node_id).  Process-global by design —
# one process IS one node in every real deployment, exactly like the
# reference's globalHTTPTrace; embedded multi-server tests that share a
# process disambiguate spans by their detail payload (drive path /
# endpoint), not nodeName.
NODE_NAME = ""


def set_node_name(name: str) -> None:
    global NODE_NAME
    NODE_NAME = name


def set_request_id(request_id: str) -> None:
    _REQUEST_ID.set(request_id)


def get_request_id() -> str:
    return _REQUEST_ID.get()


# -- causal span trees --------------------------------------------------------
#
# Beyond flat request-ID correlation, every span carries a span_id and
# a parent_id so a request's drive ops, kernel dispatches, batcher
# waits, and peer-side twins assemble into ONE tree (Dapper's causal
# model, not just its correlation model).  The parent rides beside the
# request ID: explicitly into fan-out pool threads and writer-plane
# queues (contextvars do not cross threads), and over the internode
# wire in an X-Span-Parent header beside X-Request-ID.  The request
# root's span id IS the request id, so a tree is addressable by the
# same key as its flight-recorder row.
_SPAN_PARENT: contextvars.ContextVar[str] = contextvars.ContextVar(
    "mt_span_parent", default="")

# span-id mint: a per-process prefix + counter — two allocation-free
# int ops per id, unique across the nodes of a test cluster sharing
# one process (the NODE_NAME caveat does not bite: ids, not names)
_SID_PREFIX = f"{(os.getpid() ^ time.time_ns()) & 0xffffffff:08x}"
_SID_COUNTER = itertools.count(1)


def new_span_id() -> str:
    return f"{_SID_PREFIX}-{next(_SID_COUNTER):x}"


def set_span_parent(span_id: str) -> None:
    _SPAN_PARENT.set(span_id)


def get_span_parent() -> str:
    return _SPAN_PARENT.get()


def push_span_parent(span_id: str):
    """Make ``span_id`` the parent for spans emitted in this context;
    returns a token for :func:`pop_span_parent` (the internode client
    leg brackets its roundtrip with this so peer-side spans nest under
    the client-side internode span)."""
    return _SPAN_PARENT.set(span_id)


def pop_span_parent(token) -> None:
    _SPAN_PARENT.reset(token)


# Always-on causal span ring: compact tuples, appended even with zero
# subscribers (the flight-recorder discipline — the evidence for a
# breach-window request must already be on hand when the forensic
# trigger fires).  Slot layout:
#   (start_ns, request_id, span_id, parent_id, type, name, dur_ns,
#    error, label, extra)
# ``label`` is the one attribution string worth paying for idle (drive
# endpoint / peer endpoint / plane); ``extra`` is None except for
# quorum-gating spans, which carry their compact gating tuple.
SPAN_RING_CAP = 16384

_R_START, _R_RID, _R_SID, _R_PARENT, _R_TYPE, _R_NAME, _R_DUR, \
    _R_ERR, _R_LABEL, _R_EXTRA = range(10)


class _SpanRing:
    """Fixed-slot overwrite ring (the lastminute lock-cheap model):
    appends are a list store + one int add under the GIL; a racing
    pair of appends can overwrite one slot, which minute-granularity
    tree assembly tolerates — span capture must never serialize the
    drive hot path on an observability lock."""

    __slots__ = ("_buf", "_cap", "_n")

    def __init__(self, cap: int):
        self._buf: list = [None] * cap
        self._cap = cap
        self._n = 0

    def append(self, rec: tuple) -> None:
        n = self._n
        self._buf[n % self._cap] = rec
        self._n = n + 1

    def snapshot(self) -> list:
        """Live records, oldest first (query time only)."""
        n = self._n
        if n <= self._cap:
            out = self._buf[:n]
        else:
            i = n % self._cap
            out = self._buf[i:] + self._buf[:i]
        return [r for r in out if r is not None]

    def appended_total(self) -> int:
        return self._n

    def clear(self) -> None:
        self._buf = [None] * self._cap
        self._n = 0


SPANS = _SpanRing(SPAN_RING_CAP)


def ring_append(rid: str, span_id: str, parent_id: str, trace_type: str,
                name: str, start_ns: int, dur_ns: int, error: str = "",
                label: str = "", extra=None) -> None:
    """Append one compact causal-span tuple (the idle-path emit: span
    dict construction stays behind :func:`active`)."""
    SPANS.append((start_ns, rid, span_id, parent_id, trace_type, name,
                  dur_ns, error, label, extra))


# deep-span activation bookkeeping: a default (http-only) `admin trace`
# stream must not light up subsystem-span construction — locally or on
# peers — just to have the filter drop everything.  Consumers that only
# want http records register an opt-out; peer ring polls declare their
# wanted types and only lease deep capture when they include one.
_DEEP_OPT_OUT = 0
_deep_mu = threading.Lock()
_deep_ring_until = 0.0

DEEP_RING_LEASE_S = 10.0


@contextlib.contextmanager
def http_only_consumer():
    """Mark one hub subscriber as http-only for its lifetime: it keeps
    http traces flowing (PubSub.active) without paying for subsystem
    spans it would filter out anyway."""
    global _DEEP_OPT_OUT
    with _deep_mu:
        _DEEP_OPT_OUT += 1
    try:
        yield
    finally:
        with _deep_mu:
            _DEEP_OPT_OUT -= 1


def lease_deep_ring(seconds: float = DEEP_RING_LEASE_S) -> None:
    """A peer poll wants subsystem spans: capture them for a while
    (the trace ring's lease pattern, utils/pubsub.py since())."""
    global _deep_ring_until
    _deep_ring_until = time.monotonic() + seconds


def active() -> bool:
    """Single-predicate guard for SUBSYSTEM span emission: True only
    when a consumer that wants deep spans exists — a hub subscriber
    that did not opt out, or a recent peer poll that asked for deep
    types.  HTTP traces gate on PubSub.active instead (any consumer)."""
    if HTTP_TRACE._n_subs > _DEEP_OPT_OUT:
        return True
    until = _deep_ring_until
    if not until:
        return False
    return time.monotonic() < until


# query parameters never to leak into traces/audit: presigned-URL
# credentials (SigV4 X-Amz-Signature/X-Amz-Credential + the session
# token, SigV2 Signature) are replayable until they expire — the same
# contract as the header redaction above, applied to the query string
_REDACTED_QUERY = {"x-amz-signature", "x-amz-credential",
                   "x-amz-security-token", "signature"}


def redact_headers(headers: Dict[str, str]) -> Dict[str, str]:
    return {k: ("*REDACTED*" if k.lower() in _REDACTED_HEADERS else v)
            for k, v in headers.items()}


def redact_query(query: Dict[str, str]) -> Dict[str, str]:
    return {k: ("*REDACTED*" if k.lower() in _REDACTED_QUERY else v)
            for k, v in query.items()}


def redact_query_string(raw: str) -> str:
    """``k=v&k=v`` form of :func:`redact_query` (trace rawQuery)."""
    if not raw:
        return raw
    out = []
    for kv in raw.split("&"):
        k, sep, v = kv.partition("=")
        if sep and k.lower() in _REDACTED_QUERY:
            v = "*REDACTED*"
        out.append(f"{k}{sep}{v}")
    return "&".join(out)


def make_trace(node_name: str, func_name: str, *, method: str, path: str,
               raw_query: str, client: str, req_headers: Dict[str, str],
               status_code: int, resp_headers: Dict[str, str],
               input_bytes: int, output_bytes: int,
               start_ns: int, ttfb_ns: int, duration_ns: int,
               trace_type: str = "http", error: str = "",
               request_id: str = "",
               detail: Dict[str, Any] | None = None) -> Dict[str, Any]:
    """Build a trace.Info-shaped record (pkg/trace/trace.go:26-40).
    ``detail`` (when present) lands under the ``detail`` key — the
    request X-ray publishes its per-stage timeline there
    (``detail.stages``, obs/stages.py)."""
    return {
        **({"detail": detail} if detail else {}),
        "type": trace_type,
        "nodeName": node_name,
        "funcName": func_name,
        "time": start_ns,
        "requestID": request_id or get_request_id(),
        "reqInfo": {
            "time": start_ns,
            "method": method,
            "path": path,
            "rawQuery": redact_query_string(raw_query),
            "client": client,
            "headers": redact_headers(req_headers),
        },
        "respInfo": {
            "time": start_ns + duration_ns,
            "statusCode": status_code,
            "headers": dict(resp_headers),
        },
        "callStats": {
            "inputBytes": input_bytes,
            "outputBytes": output_bytes,
            "latency_ns": duration_ns,
            "timeToFirstByte_ns": ttfb_ns,
        },
        **({"error": error} if error else {}),
    }


def make_span(trace_type: str, func_name: str, *, start_ns: int,
              duration_ns: int, input_bytes: int = 0,
              output_bytes: int = 0, error: str = "",
              detail: Dict[str, Any] | None = None,
              span_id: str = "",
              parent_id: str | None = None,
              _ring: bool = True) -> Dict[str, Any]:
    """Subsystem span (the ``mc admin trace -a`` record shape):
    smaller than an HTTP trace.Info but keyed the same so one consumer
    handles both.  ``detail`` lands under the trace-type key, e.g.
    ``{"storage": {"drive": ..., "volume": ..., "path": ...}}``.

    Every span is a causal-tree node: ``spanID`` (minted here unless
    the caller pre-minted one to propagate, e.g. the internode client
    leg) and ``parentID`` (the contextvar parent unless overridden).
    The span is also appended to the always-on causal ring, so active
    consumers and the ring see the same ids."""
    rid = get_request_id()
    sid = span_id or new_span_id()
    par = get_span_parent() if parent_id is None else parent_id
    if rid and _ring:
        label = ""
        if detail:
            label = str(detail.get("drive") or detail.get("endpoint")
                        or "")
        SPANS.append((start_ns, rid, sid, par, trace_type, func_name,
                      duration_ns, error, label, None))
    return {
        "type": trace_type,
        "nodeName": NODE_NAME,
        "funcName": func_name,
        "time": start_ns,
        "requestID": rid,
        "spanID": sid,
        "parentID": par,
        "callStats": {
            "inputBytes": input_bytes,
            "outputBytes": output_bytes,
            "latency_ns": duration_ns,
        },
        **({trace_type: detail} if detail else {}),
        **({"error": error} if error else {}),
    }


# -- the span helper ----------------------------------------------------------

# trace type -> the histogram family its helper spans observe, labelled
# by the two halves of the span's ``<op>.<leg>`` name
LEG_FAMILIES = {"tpu": "mt_tpu_leg_seconds",
                "read": "mt_read_leg_seconds"}
# each family's twin for the CPU time of the thread that ran the leg:
# ``mt_x_leg_seconds`` -> ``mt_x_leg_cpu_seconds``
_LEG_CPU_FAMILIES = {t: f[:-len("seconds")] + "cpu_seconds"
                     for t, f in LEG_FAMILIES.items()}
# The CPU clock is SAMPLED: one span in this many of each name reads
# it, the first one always.  ``time.thread_time_ns()`` has no vDSO
# path; where a sandbox traps the syscall (gVisor, the benchmark's
# machines) it costs 6 us alone and 36 us under load, 60x
# ``monotonic_ns()``, with the interpreter lock held: read at both ends
# of every span it took ~5 % of ``n16.small-zipf``'s ``ops_per_s``
# (PERF.md section 6, PR 36).  A sampled span observes its CPU time AND
# its wall into the twin (``clock="cpu"`` / ``"wall"``), so the share is
# read inside one family, over the same spans.
CPU_SAMPLE_EVERY = 16
_cpu_seen: dict = {}     # span name -> spans entered (races only jitter)

# ``factory(name)`` -> a context manager entered and exited around every
# helper span.  ops/device.py installs jax.profiler.TraceAnnotation when
# it is imported (a ``--backend numpy`` server never imports it, and
# this package never imports JAX); with no profiler session the
# annotation is a no-op.
_ANNOTATOR = None


def set_annotator(factory) -> None:
    global _ANNOTATOR
    _ANNOTATOR = factory


class span:
    """``with span("tpu", "encode.prep", nbytes=n):`` — time one leg.

    On exit, from the one interval: a compact tuple in the always-on
    ring under the request id and span parent of the calling context
    (``trace-tree`` shows the leg with no subscriber connected); one
    observation of the type's leg histogram; the annotator's event
    ``mt:<name>`` when one is installed.  The full span dict is built
    only behind :func:`active`; ``detail`` is then called for its
    type-keyed payload.  An exception passing through is recorded as
    the span's ``error`` and propagates.  ``dur_ns``, ``cpu_ns`` and
    ``error`` stay readable after the block for callers that also count
    the interval.  A leg's wall includes the time its thread waited for
    the GIL; ``cpu_ns`` (``time.thread_time_ns``; None unless this span
    was one of the ``CPU_SAMPLE_EVERY`` that read the clock) does not,
    so wall - CPU of a leg that makes no blocking call is that wait."""

    __slots__ = ("trace_type", "name", "nbytes", "detail", "start_ns",
                 "dur_ns", "cpu_ns", "error", "_t0", "_c0", "_ann")

    def __init__(self, trace_type: str, name: str, nbytes: int = 0,
                 detail=None):
        self.trace_type = trace_type
        self.name = name
        self.nbytes = nbytes
        self.detail = detail
        self.start_ns = self.dur_ns = 0
        self.cpu_ns = None
        self.error = ""
        self._ann = None

    def __enter__(self):
        if _ANNOTATOR is not None:
            self._ann = _ANNOTATOR("mt:" + self.name)
            self._ann.__enter__()
        self.start_ns = time.time_ns()
        self._c0 = time.thread_time_ns() if cpu_sampled(self.name) else -1
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, et, ev, tb):
        self.dur_ns = dur = time.monotonic_ns() - self._t0
        if self._c0 >= 0:
            self.cpu_ns = time.thread_time_ns() - self._c0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
            self._ann = None
        if et is not None:
            self.error = f"{et.__name__}: {ev}"
        observe_span(self.trace_type, self.name, self.start_ns, dur,
                     self.cpu_ns, self.error, int(self.nbytes), self.detail)
        return False


def cpu_sampled(name: str) -> bool:
    """Whether this span of ``name`` reads its thread's CPU clock: one in
    ``CPU_SAMPLE_EVERY`` of each name, the first one always."""
    seen = _cpu_seen.get(name, 0)
    _cpu_seen[name] = seen + 1
    return not seen % CPU_SAMPLE_EVERY


def observe_span(trace_type: str, name: str, start_ns: int, dur_ns: int,
                 cpu_ns: int | None = None, error: str = "",
                 nbytes: int = 0, detail=None) -> None:
    """What a :class:`span` records on its exit, for an interval timed
    elsewhere (a native read wave's per-item clocks,
    storage/xl_storage.py read_shard_wave): the ring tuple, the leg
    histogram and its CPU twin (when ``cpu_ns`` was sampled) and, behind
    :func:`active`, the full span.  ``start_ns`` is on the wall clock."""
    family = LEG_FAMILIES.get(trace_type)
    if family:
        op, _, leg = name.partition(".")
        labels = {"op": op, "leg": leg}
        _metrics.observe(family, labels, dur_ns / 1e9,
                         buckets=KERNEL_BUCKETS)
        if cpu_ns is not None:
            twin = _LEG_CPU_FAMILIES[trace_type]
            _metrics.observe(twin, {**labels, "clock": "cpu"},
                             cpu_ns / 1e9, buckets=KERNEL_BUCKETS)
            _metrics.observe(twin, {**labels, "clock": "wall"},
                             dur_ns / 1e9, buckets=KERNEL_BUCKETS)
    rid = _REQUEST_ID.get()
    sid = ""
    if rid:
        sid = new_span_id()
        ring_append(rid, sid, _SPAN_PARENT.get(), trace_type, name,
                    start_ns, dur_ns, error)
    if active():
        publish_span(make_span(
            trace_type, name, start_ns=start_ns, duration_ns=dur_ns,
            input_bytes=nbytes, error=error, span_id=sid, _ring=False,
            detail=detail() if detail else None))


def publish(info: Dict[str, Any]) -> None:
    HTTP_TRACE.publish(info)


def publish_span(span: Dict[str, Any]) -> None:
    HTTP_TRACE.publish(span)


def subscribers() -> int:
    return HTTP_TRACE.num_subscribers


def now_ns() -> int:
    return time.time_ns()
