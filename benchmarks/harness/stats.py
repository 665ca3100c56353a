"""Metric arithmetic on the client's records.  Pure functions on plain
lists, so ``benchmarks/tests`` can pin them on fixed inputs.

A record is ``[thread, op, t_start, t_end, size, ok]`` on the machine's
monotonic clock (CLOCK_MONOTONIC is one clock for every process of a
host), written by ``client.py``.
"""

from __future__ import annotations

import math

T, OP, T0, T1, SIZE, OK = range(6)


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile (the smallest value with at least q% of
    the sample at or below it); None on an empty sample."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile's rank."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def tail_percentile(values: list[float], q: float,
                    min_beyond: int = 10) -> float | None:
    """The q-th percentile only where at least ``min_beyond`` samples lie
    beyond it (choosing-metrics: the highest percentile that has ten
    samples beyond it); None otherwise, and the metric is left out."""
    if samples_beyond(len(values), q) < min_beyond:
        return None
    return percentile(values, q)


def in_window(records: list, t0: float, t1: float) -> list:
    """Operations COMPLETED inside [t0, t1]."""
    return [r for r in records if t0 <= r[T1] <= t1]


def latencies_ms(records: list, op: str | None) -> list[float]:
    return [(r[T1] - r[T0]) * 1e3 for r in records
            if r[OK] and (op is None or r[OP] == op)]


def rate_per_s(records: list, seconds: float, op: str | None = None) -> float:
    return sum(1 for r in records
               if r[OK] and (op is None or r[OP] == op)) / seconds


def generator_overhead_share(records: list, t0: float, t1: float) -> float:
    """Share of the window the client threads spent between a reply read
    in full and the next request's first byte: the generator's own cost
    (choosing the op, signing, bookkeeping), which a closed loop adds to
    every round.  Records of all threads; gaps clipped to the window."""
    by_thread: dict = {}
    for r in records:
        by_thread.setdefault(r[T], []).append(r)
    gap = 0.0
    for rs in by_thread.values():
        rs.sort(key=lambda r: r[T0])
        for a, b in zip(rs, rs[1:]):
            lo, hi = max(a[T1], t0), min(b[T0], t1)
            if hi > lo:
                gap += hi - lo
    threads = len(by_thread)
    return gap / (threads * (t1 - t0)) if threads and t1 > t0 else 0.0


def evaluate(spec: dict, window: list, seconds: float,
             setup_s: float) -> tuple[float | None, int]:
    """One end-to-end (or client-read per-layer) metric from its data
    file's ``stat``.  Returns (value, samples)."""
    kind = spec["stat"]
    if kind == "setup":
        return setup_s, 1
    if kind == "rate":
        rate = rate_per_s(window, seconds, spec.get("op"))
        return rate, round(rate * seconds)
    if kind == "latency_percentile":
        lat = latencies_ms(window, spec["op"])
        return tail_percentile(lat, spec["q"],
                               spec.get("min_beyond", 0)), len(lat)
    raise ValueError(f"unknown stat {kind!r}")
