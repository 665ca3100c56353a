"""From a profiler trace to numbers: device busy time, time per program,
idle gaps and what the host did in them.

``load`` reads the ``.xplane.pb`` JAX's profiler writes, with
``jaxlib._profile_data`` alone (no ``import jax``: the harness process must
never hold a chip), into plain lists; ``summarize`` is pure arithmetic on
those lists, pinned by ``benchmarks/tests`` on ``fixtures/trace_small.json``.

Planes: ``{"name": str, "stats": {..}, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns, hlo_module or ""], ...]}]}``.

Which events count as device work:
  * a plane ``/device:TPU:<n>`` is one chip; its line ``XLA Ops`` holds
    one event per operation the chip ran, ``XLA Modules`` one per program
    (``jit_<name>(<fingerprint>)``).  Busy time is the union of the
    ``XLA Ops`` intervals; per-program time comes from ``XLA Modules``.
    All planes of one trace share one clock that counts from the
    profile's start (seen in a v5e trace, PR 22).
  * a CPU trace (the rehearsal) has no device plane: XLA:CPU thunks are
    host events that carry an ``hlo_module`` stat.  With ``rehearse`` they
    are taken as one pseudo-chip so the wiring can be rehearsed.  Without
    it a trace that holds no TPU plane, or whose device events all lie
    outside the slice, raises ``TraceError``: no host number ever stands
    under a device metric's name.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

import numpy as np

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


class TraceError(Exception):
    """The trace cannot give device numbers."""
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> list[dict]:
    from jaxlib._profile_data import ProfileData
    planes = []
    for pl in ProfileData.from_file(path).planes:
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                mod = ""
                if not pl.name.startswith("/device:"):
                    for k, v in e.stats:
                        if k == "hlo_module":
                            mod = str(v)
                            break
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            mod])
            lines.append({"name": ln.name, "events": evs})
        stats = {}
        try:
            stats = {k: v for k, v in pl.stats
                     if isinstance(v, (int, float, str))}
        except Exception:  # noqa: BLE001 — plane stats are optional detail
            pass
        planes.append({"name": pl.name, "stats": stats, "lines": lines})
    return planes


def union_seconds(intervals: list[tuple[float, float]]
                  ) -> tuple[float, list[tuple[float, float]]]:
    """Total covered length and the merged intervals (ns in, ns out; the
    total in seconds)."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return (sum(e - s for s, e in merged) / 1e9,
            [(s, e) for s, e in merged])


def _chips(planes: list[dict], rehearse: bool) -> list[dict]:
    """One {"name", "ops": [(name, s, e)], "programs": [(name, s, e)]}
    per chip."""
    chips = []
    for pl in planes:
        if not pl["name"].startswith("/device:TPU:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        if OPS_LINE not in lines:
            continue
        ops = [(n, s, s + d) for n, s, d, _ in lines[OPS_LINE]]
        progs = [(_FINGERPRINT.sub("", n), s, s + d)
                 for n, s, d, _ in lines.get(MODULES_LINE, [])]
        chips.append({"name": pl["name"], "ops": ops, "programs": progs})
    if chips:
        return chips
    if not rehearse:
        raise TraceError(
            f"no /device:TPU: plane with an {OPS_LINE!r} line among "
            f"{[pl['name'] for pl in planes]}")
    ops = []                                  # CPU rehearsal: one pseudo-chip
    for pl in planes:
        if pl["name"].startswith("/host:"):
            for ln in pl["lines"]:
                ops += [(n, s, s + d, m) for n, s, d, m in ln["events"]
                        if m]
    if not ops:
        return []
    return [{"name": "/host:CPU (XLA:CPU thunks, rehearsal only)",
             "ops": [(n, s, e) for n, s, e, _ in ops],
             "programs": [(m, s, e) for _, s, e, m in ops]}]


def _host_events(planes: list[dict]):
    names, starts, ends = [], [], []
    for pl in planes:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for n, s, d, m in ln["events"]:
                if d > 0 and not m:
                    names.append(n.split("(")[0][:60])
                    starts.append(s)
                    ends.append(s + d)
    return names, np.array(starts), np.array(ends)


def _window_ns(planes: list[dict], chips: list[dict],
               slice_unix_ns: tuple[int, int] | None) -> tuple[float, float]:
    """The slice on the trace's clock.  Event times count from the
    profile's start, which the ``Task Environment`` plane gives in unix
    ns; the shim says when its slice began and ended on the same clock
    (starting and stopping the profiler take seconds themselves)."""
    for pl in planes:
        st = pl["stats"]
        if "profile_start_time" in st and "profile_stop_time" in st:
            p0, p1 = st["profile_start_time"], st["profile_stop_time"]
            if slice_unix_ns:
                return (float(max(slice_unix_ns[0], p0) - p0),
                        float(min(slice_unix_ns[1], p1) - p0))
            return 0.0, float(p1 - p0)
    evs = [x for c in chips for x in c["ops"]]
    return min(s for _, s, _ in evs), max(e for _, _, e in evs)


def _clip(evs: list[tuple], w0: float, w1: float) -> list[tuple]:
    return [(n, max(s, w0), min(e, w1)) for n, s, e in evs
            if e > w0 and s < w1]


_HLO = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = .*?\s([a-z][a-z0-9\-]*)\(")
LONG_GAP_NS = 10e6      # gaps this long are looked up in the host planes


def op_label(name: str) -> str:
    """On a TPU an ``XLA Ops`` event is named by its whole HLO line;
    keep the instruction's name and its opcode: ``_run_nat (custom-call)``."""
    m = _HLO.match(name)
    return f"{m.group(1)} ({m.group(2)})" if m else name[:80]


def _gap_name(g0: float, g1: float, progs: list, pstarts: list,
              hnames, hstart, hend) -> str:
    """What an idle gap is filed under: a long one under the host event
    that both covers it and lies inside it, where the host planes have
    one; any other under the program the chip ran next, i.e. what it
    was waiting to be handed."""
    if g1 - g0 >= LONG_GAP_NS and len(hstart):
        ov = np.minimum(hend, g1) - np.maximum(hstart, g0)
        score = np.where(ov > 0, ov * ov / (hend - hstart), 0.0)
        i = int(score.argmax())
        if ov[i] >= 0.5 * (g1 - g0) and ov[i] >= 0.5 * (hend[i] - hstart[i]):
            return "host: " + hnames[i]
    i = bisect.bisect_right(pstarts, g1) - 1
    if i >= 0 and progs[i][2] > g1:
        return "waiting for " + progs[i][0]
    return "after the last program of the slice" if i >= 0 \
        else "before the first program of the slice"


def summarize(planes: list[dict],
              slice_unix_ns: tuple[int, int] | None = None,
              top: int = 10, rehearse: bool = False) -> dict | None:
    """None when the chips ran nothing in the slice."""
    chips = _chips(planes, rehearse)
    if not any(c["ops"] for c in chips):
        return None
    w0, w1 = _window_ns(planes, chips, slice_unix_ns)
    ext0 = min(s for c in chips for _, s, _ in c["ops"])
    ext1 = max(e for c in chips for _, _, e in c["ops"])
    if ext1 <= w0 or ext0 >= w1:
        if not rehearse:
            raise TraceError(
                f"every device event lies outside the slice (events "
                f"{ext0:.0f}-{ext1:.0f} ns, slice {w0:.0f}-{w1:.0f} ns): "
                f"the chip was idle throughout or is on another clock")
        w0, w1 = ext0, ext1     # XLA:CPU thunks: take their own extent
    for c in chips:
        c["ops"] = _clip(c["ops"], w0, w1)
        c["programs"] = sorted(_clip(c["programs"], w0, w1),
                               key=lambda p: p[1])
    if not any(c["ops"] for c in chips):
        return None
    window_s = (w1 - w0) / 1e9
    hnames, hstart, hend = _host_events(planes)
    per_chip, programs, op_time, gap_time = [], {}, {}, {}
    for c in chips:
        busy_s, merged = union_seconds([(s, e) for _, s, e in c["ops"]])
        per_chip.append({"chip": c["name"], "busy_s": busy_s,
                         "ops": len(c["ops"]), "gaps": len(merged) + 1})
        for n, s, e in c["programs"]:
            p = programs.setdefault(n, [0.0, 0])
            p[0] += (e - s) / 1e9
            p[1] += 1
        for n, s, e in c["ops"]:
            n = op_label(n)
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
        pstarts = [p[1] for p in c["programs"]]
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 > g0:
                what = _gap_name(g0, g1, c["programs"], pstarts,
                                 hnames, hstart, hend)
                gap_time[what] = gap_time.get(what, 0.0) + (g1 - g0) / 1e9
    n = len(per_chip)
    busy = sum(c["busy_s"] for c in per_chip) / n

    def rank(d: dict) -> list:
        return sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "chips": n, "window_s": window_s, "busy_s": busy,
        "busy_s_total": sum(c["busy_s"] for c in per_chip),
        "per_chip": per_chip,
        "programs": [[k, v[0] / n, v[1]] for k, v in sorted(
            programs.items(), key=lambda kv: -kv[1][0])[:top]],
        "device_ops": [[k, v / n] for k, v in rank(op_time)],
        "idle_gaps": [[k, v / n] for k, v in rank(gap_time)],
    }


def main(argv: list[str]) -> int:
    """python -m benchmarks.harness.trace_reduce <trace dir or .pb or .json>:
    print the summary, and with --structure the planes and lines
    (--rehearse: a CPU trace)."""
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".json"):
        with open(path) as f:
            planes = json.load(f)
    else:
        planes = load(path)
    if "--structure" in argv:
        for pl in planes:
            print("PLANE", pl["name"], pl["stats"])
            for ln in pl["lines"]:
                print("  LINE", repr(ln["name"]), len(ln["events"]),
                      ln["events"][:3])
    print(json.dumps(summarize(planes, rehearse="--rehearse" in argv),
                     indent=1))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
