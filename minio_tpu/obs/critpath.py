"""Quorum critical-path attribution (the tail-at-scale discipline).

Quorum systems have a distinctive latency law: the k-th fastest of n
children determines completion, so mean per-drive latency is the wrong
signal — what matters is which child *gated* each fan-out and how far
the stragglers trailed the quorum point (Dean & Barroso's tail-at-scale
argument applied to erasure fan-outs; Dapper's critical-path analysis
applied to span trees).

Every quorum reduction point — the erasure write fan-out and read
quorum (objectlayer/erasure_object.py), the writer-plane drain
(storage/writers.py), peer fan-outs over internode RPC
(parallel/peer.py) — calls :func:`record` with its children's
completion times.  One call produces the three surfaces the ISSUE
names:

  * scrape families ``mt_quorum_gating_total{plane,drive}`` (which
    child the fan-out wall ended on) and
    ``mt_quorum_straggler_seconds{plane}`` (how far the tail trailed
    the quorum-deciding k-th completion — the time a quorum-aware
    commit plane could shave, the evidence ROADMAP's group-commit item
    needs);
  * a ``gating`` span in the causal tree (compact ring tuple always;
    a full span dict only when a deep-trace consumer is active);
  * a compact per-request row on the armed StageClock, rendered into
    the request's flight-recorder record.

Reconciliation contract: ``wall_ns`` is measured with the same
monotonic clock as the StageClock stage that encloses the reduction,
and the recorded child durations are offsets inside it — so
``kth_ns <= wall_ns <= stage wall`` holds exactly (pinned by
tests/test_trace_tree.py) the same way the serial stage vector plus
``other`` reconciles with the request total.  The stage WALL, not its
entry in the serial vector: that entry is exclusive self time, and the
stages charged inside the fan-out (``write_enqueue`` when an enqueue
parks) are subtracted from it while the gating wall spans them.

Idle contract: with no deep-trace consumer, one :func:`record` call is
a sort of the (few) completion offsets, two metric updates, one
compact ring append, and one list append on the clock — no dict is
built on the hot path.
"""

from __future__ import annotations

import time

from ..admin.metrics import GLOBAL as _metrics
from . import stages as _stages
from . import trace as _trace

# straggler-trail buckets: trails run from microseconds (tmpfs) to the
# hundreds of ms a genuinely sick drive adds
STRAGGLER_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                     0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

# compact gating-row layout (StageClock.gatings + the span ring's
# ``extra`` slot; dict-shaped rows are rendered at query time)
G_PLANE, G_K, G_N, G_DRIVE, G_KTH_DRIVE, G_KTH_NS, G_WALL_NS, \
    G_TRAIL_NS = range(8)


def drive_label(disk) -> str:
    """One attribution string per child: a local drive's endpoint, a
    remote drive/peer client's endpoint, else the repr tail."""
    for attr in ("_endpoint", "endpoint"):
        v = getattr(disk, attr, None)
        if callable(v):        # wrapper disks (HealthDisk, SlowDisk,
            try:               # RemoteStorage) expose endpoint()
                v = v()
            except Exception:  # noqa: BLE001 — label only, never fail an op
                continue
        if isinstance(v, str) and v:
            return v
    return type(disk).__name__


def render_row(row: tuple) -> dict:
    """Query-time dict shape for one compact gating row (flight
    recorder, trace-tree route, forensic bundles)."""
    return {
        "plane": row[G_PLANE],
        "k": row[G_K],
        "n": row[G_N],
        "drive": row[G_DRIVE],
        "kthDrive": row[G_KTH_DRIVE],
        "kthNs": row[G_KTH_NS],
        "wallNs": row[G_WALL_NS],
        "trailNs": row[G_TRAIL_NS],
    }


def record(plane: str, k: int, labels: list, ends_ns: list,
           t0_ns: int, errs: list | None = None) -> tuple | None:
    """Record one quorum reduction.

    ``labels[i]`` names child i; ``ends_ns[i]`` is its completion in
    absolute monotonic ns (0/None = never completed); ``errs[i]``
    (when given) excludes failed children from the quorum ordering —
    an erroring drive cannot have been the quorum decider.  ``k`` is
    the reduction's quorum; ``t0_ns`` the fan-out start on the same
    monotonic clock.

    Returns the compact gating row, or None when fewer than k children
    completed (the reduction failed — there is no critical path to
    attribute)."""
    done = []
    for i, end in enumerate(ends_ns):
        if not end:
            continue
        if errs is not None and errs[i] is not None:
            continue
        # drain-style reductions (writer-plane settle vectors) may see
        # children that completed BEFORE the reduction began; clamping
        # to t0 keeps offsets non-negative and the reconciliation
        # invariant kth_ns <= wall_ns <= enclosing-stage_ns intact
        done.append((end if end > t0_ns else t0_ns, labels[i]))
    k = max(1, min(k, len(done))) if done else k
    if len(done) < max(1, k):
        return None
    done.sort()
    kth_end, kth_label = done[k - 1]
    last_end, last_label = done[-1]
    row = (plane, k, len(labels), last_label, kth_label,
           kth_end - t0_ns, last_end - t0_ns, last_end - kth_end)
    _metrics.inc("mt_quorum_gating_total",
                 {"plane": plane, "drive": last_label})
    _metrics.observe("mt_quorum_straggler_seconds", {"plane": plane},
                     row[G_TRAIL_NS] / 1e9, buckets=STRAGGLER_BUCKETS)
    _stages.note_gating(row)
    rid = _trace.get_request_id()
    if rid:
        sid = _trace.new_span_id()
        start = _trace.now_ns() - row[G_WALL_NS]
        # the gating row rides the ring's ``extra`` slot so assembled
        # trees carry it even when nobody subscribed during the breach
        _trace.ring_append(rid, sid, _trace.get_span_parent(),
                           "storage", f"quorum.{plane}", start,
                           row[G_WALL_NS], "", last_label, row)
        if _trace.active():
            _trace.publish_span(_trace.make_span(
                "storage", f"quorum.{plane}", start_ns=start,
                duration_ns=row[G_WALL_NS], span_id=sid,
                detail={"gating": render_row(row)}, _ring=False))
    return row


def now_ns() -> int:
    """The reduction clock: monotonic, shared with the StageClock so
    gating offsets reconcile with the stage vector."""
    return time.monotonic_ns()
