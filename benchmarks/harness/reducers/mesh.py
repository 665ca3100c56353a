"""Device metrics of the mesh route (``server --backend mesh``: one
process over several chips) from the trace summaries of a slice.

ctx: as ``device.py``.  A summary's ``device_ops`` holds its ten longest
op labels (``trace_reduce.op_label``: ``<instruction> (<opcode>)``) as
per-chip MEAN seconds of that process, so chip-seconds are that times
its ``chips``; ``per_chip`` holds every chip's busy seconds.  A key whose
ops did not run (a one-chip route, a parent without the named kernel)
is left out, and the reader then reads nothing.
"""

from __future__ import annotations

import re

from .device import codec_min_bytes

FUSED_KERNEL = "mt_rs_fused"
_COLLECTIVE = re.compile(
    r"\((collective-permute|all-gather|all-reduce)(-start|-done)?\)$")


def _chip_seconds(sums: list[dict], match) -> float:
    return sum(sec * s["chips"] for s in sums
               for label, sec in s["device_ops"] if match(label))


def reduce(ctx: dict) -> dict:
    sums = [s for s in ctx["summaries"] if s]
    if not sums:
        return {}
    out = {}
    puts = ctx["puts"]
    fused = _chip_seconds(
        sums, lambda label: label.split(" (")[0] == FUSED_KERNEL)
    if puts and fused > 0:
        # the same least work whatever implements it, over the time of
        # the one kernel on every chip it ran on
        least_s = sum(codec_min_bytes(s, ctx["k"], ctx["m"])
                      for s in puts) / ctx["peaks"]["hbm_bytes_per_s"]
        out["rs_fused_roofline_pct"] = 100.0 * least_s / fused
    collective = _chip_seconds(sums, _COLLECTIVE.search)
    if puts and collective > 0:
        chips = sum(s["chips"] for s in sums)
        out["collective_ms_per_put"] = 1e3 * collective / chips / len(puts)
    busy = [c["busy_s"] for s in sums for c in s["per_chip"]]
    if len(busy) > 1 and sum(busy) > 0:
        out["chip_busy_skew_pct"] = \
            100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
    return out
