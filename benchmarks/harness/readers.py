"""Per-layer metric readers.  A metric's file under ``layer_metrics/``
declares its reader as data; a reader that finds nothing to read returns
None and the harness leaves the metric out of the line.

  stage    {"api": name or null (all), "stages": [..], "per": api name or
            null (all requests), "scale": 1000}
           Σ Δmt_s3_stage_seconds_sum / Δmt_s3_requests_api_total
  counter  {"num": [sel..], "den": [sel..] or absent (1), "scale": 1}
           sel = {"family": name, "labels": {label: value or [values]}}
  info     {"path": "codec.device.compile.compiles"}: admin info, after
           minus before the window, summed over processes
  trace    {"reducer": module under reducers/, "key": its output key}
  client   a ``stats.evaluate`` spec on the client's records of the window
"""

from __future__ import annotations

import importlib

from . import stats


def _match(labels: dict, want: dict) -> bool:
    for k, v in want.items():
        if v is None:
            continue
        if labels.get(k) not in (v if isinstance(v, list) else [v]):
            return False
    return True


def total(scrape: dict, sel: dict) -> float | None:
    """Sum of every sample of one scrape that the selector matches; None
    where none does."""
    hits = [v for labels, v in scrape.get(sel["family"], [])
            if _match(labels, sel.get("labels", {}))]
    return sum(hits) if hits else None


def delta(ctx: dict, sel: dict) -> float | None:
    """after - before of every sample the selector matches; None where the
    family has no matching sample in the later scrape."""
    after = total(ctx["scrape1"], sel)
    if after is None:
        return None
    return after - (total(ctx["scrape0"], sel) or 0.0)


def _ratio(ctx, num: list, den: list | None, scale: float) -> float | None:
    n = [delta(ctx, s) for s in num]
    if all(x is None for x in n):
        return None
    top = sum(x or 0.0 for x in n)
    if den is None:
        return top * scale
    bottom = sum(delta(ctx, s) or 0.0 for s in den)
    return top / bottom * scale if bottom > 0 else None


def _dig(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def read(spec: dict, ctx: dict) -> float | None:
    r = spec["reader"]
    kind = r["kind"]
    if kind == "stage":
        return _ratio(
            ctx,
            [{"family": "mt_s3_stage_seconds_sum",
              "labels": {"api": r.get("api"), "stage": r["stages"]}}],
            [{"family": "mt_s3_requests_api_total",
              "labels": {"api": r.get("per")}}],
            r.get("scale", 1.0))
    if kind == "counter":
        return _ratio(ctx, r["num"], r.get("den"), r.get("scale", 1.0))
    if kind == "info":
        vals = [(_dig(a, r["path"]), _dig(b, r["path"]))
                for b, a in zip(ctx["info0"], ctx["info1"])]
        vals = [(a, b) for a, b in vals if a is not None]
        if not vals:
            return None
        return float(sum(a - (b or 0) for a, b in vals))
    if kind == "trace":
        if not ctx.get("trace"):
            return None
        name = r.get("reducer", "device")
        cache = ctx.setdefault("_reduced", {})
        if name not in cache:
            mod = importlib.import_module(
                f"benchmarks.harness.reducers.{name}")
            cache[name] = mod.reduce(ctx["trace"])
        return cache[name].get(r["key"])
    if kind == "client":
        return stats.evaluate(r, ctx["window"], ctx["seconds"], 0.0)[0]
    raise ValueError(f"metric {spec['name']}: unknown reader kind {kind!r}")
