"""Device metrics of the PUT path from the trace summaries of a slice.

ctx: ``summaries`` (one ``trace_reduce.summarize`` result per traced
process), ``puts`` (sizes in bytes of the PUTs completed in the slice),
``k``/``m`` (the configuration's geometry), ``peaks`` (the entry of
``peaks.json`` for the device kind the servers report).
"""

from __future__ import annotations


def codec_min_bytes(size: int, k: int, m: int) -> float:
    """Least HBM traffic to encode one object: the k data shards are read
    once and the m parity shards written once; the bitrot hash rides the
    same bytes.  size * (k + m) / k."""
    return size * (k + m) / k


def reduce(ctx: dict) -> dict:
    sums = [s for s in ctx["summaries"] if s]
    if not sums:
        return {}
    chips = sum(s["chips"] for s in sums)
    busy_total = sum(s["busy_s_total"] for s in sums)
    window = sum(s["window_s"] * s["chips"] for s in sums) / chips
    out = {"device_idle_pct": 100.0 * (1.0 - busy_total / chips / window)}
    puts = ctx["puts"]
    if puts and busy_total > 0:
        out["device_ms_per_put"] = 1e3 * busy_total / len(puts)
        least_s = sum(codec_min_bytes(s, ctx["k"], ctx["m"])
                      for s in puts) / ctx["peaks"]["hbm_bytes_per_s"]
        out["codec_roofline_pct"] = 100.0 * least_s / busy_total
    return out
