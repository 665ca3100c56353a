"""Prometheus metrics — the metrics-v2 catalog
(cmd/metrics-v2.go:42-48 namespaces minio_{s3,bucket,cluster,heal,node}).

A process-wide registry of counters and histograms rendered in
Prometheus text exposition format at /minio-tpu/metrics, plus gauge
families computed at scrape time from live subsystems:

  mt_s3_*       per-API request counters, rx/tx bytes, TTFB histogram
                (minio_s3_requests_total / minio_s3_ttfb_seconds role)
  mt_bucket_*   per-bucket usage/object/version gauges and the object
                size-distribution histogram, from the data crawler's
                persisted usage cache (cmd/metrics-v2.go bucket usage
                family — the crawler computes it, the scrape exports it)
  mt_cluster_*  capacity and drive-count gauges
  mt_heal_*     background-heal progress counters (BgHealState)
  mt_node_*     inter-node RPC call/byte/error counters (internode
                family, cmd/metrics-v2.go getInterNodeMetrics)
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict

_START = time.time()

# reference TTFB buckets (cmd/metrics-v2.go:69 defaultHistogramBuckets)
TTFB_BUCKETS = (0.001, 0.003, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

# erasure-kernel wall-time buckets (mt_tpu_kernel_seconds): kernels run
# sub-ms on device and tens of ms on the host fallback
KERNEL_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

# batched-dispatch size buckets (mt_tpu_batch_blocks): erasure blocks
# per device dispatch, the BENCH trajectory's batching axis
BATCH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                 512.0, 1024.0)


class Metrics:
    def __init__(self):
        self._mu = threading.Lock()
        self._counters: dict[tuple, float] = defaultdict(float)
        # histogram key -> [bucket counts..., +Inf count, sum]
        self._hists: dict[tuple, list] = {}

    def inc(self, name: str, labels: dict[str, str] | None = None,
            value: float = 1.0) -> None:
        key = (name, tuple(sorted((labels or {}).items())))
        with self._mu:
            self._counters[key] += value

    def observe(self, name: str, labels: dict[str, str] | None = None,
                value: float = 0.0,
                buckets: tuple = TTFB_BUCKETS) -> None:
        self.observe_many(name, labels, (value,), buckets)

    def observe_many(self, name: str, labels: dict[str, str] | None,
                     values, buckets: tuple = TTFB_BUCKETS) -> None:
        """:meth:`observe` for every value of ``values`` under ONE lock
        acquisition: a fan-out folds its children's times into a family
        with one registry call, not one per child."""
        key = (name, tuple(sorted((labels or {}).items())), buckets)
        nb = len(buckets)
        with self._mu:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = [0] * (nb + 1) + [0.0]
            for value in values:
                for i, ub in enumerate(buckets):
                    if value <= ub:
                        h[i] += 1
                h[nb] += 1                # +Inf / _count
                h[-1] += value            # _sum

    def snapshot(self) -> dict[tuple, float]:
        with self._mu:
            return dict(self._counters)

    def hist_snapshot(self) -> dict[tuple, list]:
        with self._mu:
            return {k: list(v) for k, v in self._hists.items()}


GLOBAL = Metrics()


def _escape_label(v) -> str:
    """Label-value escaping per the text-format spec: backslash, double
    quote, and newline must be escaped or the scrape is unparseable."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_value(v: float) -> str:
    """Full-precision sample rendering: ``%g`` keeps only 6 significant
    digits, which quantizes fast-growing byte counters (a 1 TB
    mt_tpu_bytes_total would move in ~10 MB steps and flatline
    rate())."""
    return str(int(v)) if v == int(v) else repr(v)


def _fmt_labels(labels: tuple, extra: str = "") -> str:
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels)
    if extra:
        inner = f"{inner},{extra}" if inner else extra
    return "{" + inner + "}" if inner else ""


def render(layer=None, healer=None, config=None, api_stats=None,
           replication=None, crawler=None, node=None,
           egress=None, mrf=None, flightrec=None,
           rebalancer=None, watchdog=None, metering=None) -> str:
    """Prometheus text format: counters + histograms + live gauges.

    ``config`` (a kvconfig Config) supplies the slow-drive knobs at
    scrape time — admin SetConfigKV retunes detection live; ``api_stats``
    is the server's last-minute per-API OpWindows; ``replication`` /
    ``crawler`` export the background planes (ReplicationSys + Crawler);
    ``mrf`` is the server's MRFQueue, whose own stats feed the
    ``mt_heal_mrf_*`` counters (the sweep healer's stats keep those
    fields for renders that only hand in ``healer``).

    ``node`` names this server for federation: every sample gains a
    ``server`` label so one merged cluster document keeps per-node
    series apart (the Prometheus federation convention — honor the
    source's identity labels when aggregating).

    ``egress`` is the server's EgressRegistry (obs/egress.py): the
    ``mt_target_*`` delivery families are computed at scrape time from
    the live targets' own counters, so a server with zero configured
    targets emits NO target families at all (the idle contract)."""
    lines = [
        "# HELP mt_up Server is up.",
        "# TYPE mt_up gauge",
        "mt_up 1",
        "# HELP mt_uptime_seconds Process uptime.",
        "# TYPE mt_uptime_seconds gauge",
        f"mt_uptime_seconds {time.time() - _START:.1f}",
    ]
    counters = GLOBAL.snapshot()
    hists = GLOBAL.hist_snapshot()
    # a histogram family owns its base name AND the derived sample
    # names; a counter colliding with any of them is DROPPED from the
    # scrape — emitting it would either mint a second # TYPE line or
    # inject a duplicate/mis-shaped sample into the histogram family,
    # both of which strict text-format parsers reject (a collision is
    # a programming error; a valid scrape beats a corrupt one)
    seen_names = set()
    reserved = set()
    for (hname, _, _) in hists:
        reserved.update((hname, f"{hname}_bucket", f"{hname}_sum",
                         f"{hname}_count"))
    for (name, labels), value in sorted(counters.items()):
        if name in reserved:
            continue
        if name not in seen_names:
            lines.append(f"# TYPE {name} counter")
            seen_names.add(name)
        lines.append(f"{name}{_fmt_labels(labels)} {_fmt_value(value)}")
    for (name, labels, buckets), h in sorted(hists.items()):
        if name not in seen_names:
            lines.append(f"# TYPE {name} histogram")
            seen_names.update((name, f"{name}_bucket", f"{name}_sum",
                               f"{name}_count"))
        for i, ub in enumerate(buckets):
            le = 'le="%g"' % ub
            lines.append(
                f"{name}_bucket"
                f"{_fmt_labels(labels, le)} {h[i]}")
        le_inf = 'le="+Inf"'
        lines.append(f"{name}_bucket"
                     f"{_fmt_labels(labels, le_inf)}"
                     f" {h[len(buckets)]}")
        lines.append(f"{name}_sum{_fmt_labels(labels)}"
                     f" {_fmt_value(h[-1])}")
        lines.append(f"{name}_count{_fmt_labels(labels)}"
                     f" {h[len(buckets)]}")
    if layer is not None:
        try:
            lines += _cluster_gauges(layer)
        except Exception:  # noqa: BLE001 — metrics must never fail a scrape
            pass
        try:
            lines += _bucket_usage_gauges(layer)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
        try:
            lines += _disk_lastminute_gauges(layer, config)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
        try:
            lines += _put_pipeline_gauges(layer)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
        try:
            lines += _hot_read_gauges(layer)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    try:
        lines += _codec_batch_gauges()
    except Exception:  # noqa: BLE001 — a scrape must never fail
        pass
    try:
        lines += _memgov_gauges()
    except Exception:  # noqa: BLE001 — a scrape must never fail
        pass
    try:
        lines += _locktrace_gauges()
    except Exception:  # noqa: BLE001 — a scrape must never fail
        pass
    try:
        lines += _tls_gauges()
    except Exception:  # noqa: BLE001 — a scrape must never fail
        pass
    if api_stats is not None:
        try:
            lines += _s3_lastminute_gauges(api_stats)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if healer is not None or mrf is not None:
        try:
            lines += _heal_counters(healer, mrf)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if healer is not None:
        try:
            lines += _progress_gauges("mt_heal", healer.progress)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if crawler is not None:
        try:
            lines += _scanner_gauges(crawler)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if replication is not None:
        try:
            lines += _replication_gauges(replication)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if rebalancer is not None:
        try:
            lines += _rebalance_metrics(rebalancer)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if egress is not None:
        try:
            lines += _egress_metrics(egress)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if flightrec is not None:
        try:
            lines += _flight_gauges(flightrec)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if watchdog is not None:
        try:
            lines += _watchdog_metrics(watchdog)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    if metering is not None:
        try:
            lines += _metering_gauges(metering)
        except Exception:  # noqa: BLE001 — a scrape must never fail
            pass
    text = "\n".join(lines) + "\n"
    if node:
        text = _with_server_label(text, node)
    return text


def _with_server_label(text: str, node: str) -> str:
    """Stamp ``server="<node>"`` onto every sample line of an already
    rendered exposition document (comment lines untouched).  Values
    never contain spaces, so the last space splits sample from value
    even when a label value embeds one."""
    esc = _escape_label(node)
    out = []
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            out.append(ln)
            continue
        sp = ln.rfind(" ")
        head, value = ln[:sp], ln[sp + 1:]
        if head.endswith("}"):
            head = f'{head[:-1]},server="{esc}"}}'
        else:
            head = f'{head}{{server="{esc}"}}'
        out.append(f"{head} {value}")
    return "\n".join(out) + "\n"


def merge_expositions(docs: list) -> str:
    """Merge per-node exposition documents into one cluster document:
    exactly one ``# TYPE``/``# HELP`` per family, samples regrouped
    under their family (the text format requires a family's samples to
    be contiguous — a naive concatenation would interleave them)."""
    order: list = []
    meta: dict = {}         # family -> comment lines (one per kind)
    samples: dict = {}      # family -> sample lines

    def ensure(fam: str) -> None:
        if fam not in meta:
            meta[fam] = []
            samples[fam] = []
            order.append(fam)

    for doc in docs:
        current = None
        for ln in doc.splitlines():
            if not ln.strip():
                continue
            if ln.startswith(("# TYPE ", "# HELP ")):
                parts = ln.split(None, 3)
                fam, kind = parts[2], parts[1]
                ensure(fam)
                if not any(m.split(None, 3)[1] == kind
                           for m in meta[fam]):
                    meta[fam].append(ln)
                current = fam
                continue
            if ln.startswith("#"):
                continue
            name = ln.split("{", 1)[0].split(" ", 1)[0]
            # histogram-derived names (_bucket/_sum/_count) group with
            # the declaring family; anything else starts its own
            if current is None or not name.startswith(current):
                current = name
                ensure(current)
            samples[current].append(ln)
    out = []
    for fam in order:
        out.extend(meta[fam])
        out.extend(samples[fam])
    return "\n".join(out) + "\n"


def _cluster_gauges(layer) -> list[str]:
    disks = _collect_disks(layer)
    online = sum(1 for d in disks if d is not None)
    lines = [
        "# TYPE mt_cluster_disk_online_total gauge",
        f"mt_cluster_disk_online_total {online}",
        "# TYPE mt_cluster_disk_offline_total gauge",
        f"mt_cluster_disk_offline_total {len(disks) - online}",
    ]
    total = free = 0
    for d in disks:
        if d is None:
            continue
        try:
            info = d.disk_info()
            total += info.total
            free += info.free
        except Exception:  # noqa: BLE001
            continue
    lines += [
        "# TYPE mt_cluster_capacity_raw_total_bytes gauge",
        f"mt_cluster_capacity_raw_total_bytes {total}",
        "# TYPE mt_cluster_capacity_raw_free_bytes gauge",
        f"mt_cluster_capacity_raw_free_bytes {free}",
    ]
    return lines


def _bucket_usage_gauges(layer) -> list[str]:
    """Per-bucket usage from the crawler's persisted cache (the
    reference exports bucketUsageTotalBytes / bucketUsageObjectsTotal /
    bucketObjectSizeDistribution the same way: the scanner computes,
    the scrape reads)."""
    from ..background.crawler import load_usage
    usage = load_usage(layer)
    if usage is None:
        return []
    lines = [
        "# TYPE mt_cluster_usage_last_update_timestamp_seconds gauge",
        "mt_cluster_usage_last_update_timestamp_seconds "
        f"{usage.last_update_ns / 1e9:.3f}",
        "# TYPE mt_cluster_usage_object_total gauge",
        f"mt_cluster_usage_object_total {usage.objects_total_count}",
        "# TYPE mt_cluster_usage_total_bytes gauge",
        f"mt_cluster_usage_total_bytes {usage.objects_total_size}",
        "# TYPE mt_bucket_usage_total_bytes gauge",
        "# TYPE mt_bucket_usage_object_total gauge",
        "# TYPE mt_bucket_usage_version_total gauge",
        "# TYPE mt_bucket_objects_size_distribution gauge",
    ]
    # emit after the TYPE block so each family groups correctly
    for b in sorted(usage.bucket_usage):
        u = usage.bucket_usage[b]
        lines.append(f'mt_bucket_usage_total_bytes{{bucket="{b}"}}'
                     f" {u.size}")
        lines.append(f'mt_bucket_usage_object_total{{bucket="{b}"}}'
                     f" {u.objects_count}")
        lines.append(f'mt_bucket_usage_version_total{{bucket="{b}"}}'
                     f" {u.versions_count}")
        for rng in sorted(u.histogram):
            lines.append(
                "mt_bucket_objects_size_distribution"
                f'{{bucket="{b}",range="{rng}"}} {u.histogram[rng]}')
    if usage.pools_usage:
        # elastic topology: per-pool residency from the same scan —
        # skew between pools is what drives the rebalancer.  A
        # non-pooled deployment's usage doc has no pools section, so
        # the families stay absent (idle contract).
        lines += ["# TYPE mt_pool_usage_bytes gauge",
                  "# TYPE mt_pool_usage_objects gauge"]
        for pid in sorted(usage.pools_usage):
            u = usage.pools_usage[pid]
            pl = _fmt_labels((("pool", pid),))
            lines.append(f"mt_pool_usage_bytes{pl}"
                         f" {u.get('bytes', 0)}")
            lines.append(f"mt_pool_usage_objects{pl}"
                         f" {u.get('objects', 0)}")
    return lines


def _heal_counters(healer, mrf=None) -> list[str]:
    lines = []
    if healer is not None:
        st = healer.stats
        lines += [
            "# TYPE mt_heal_objects_scanned_total counter",
            f"mt_heal_objects_scanned_total {st.objects_scanned}",
            "# TYPE mt_heal_objects_healed_total counter",
            f"mt_heal_objects_healed_total {st.objects_healed}",
            "# TYPE mt_heal_objects_failed_total counter",
            f"mt_heal_objects_failed_total {st.objects_failed}",
            "# TYPE mt_heal_cycles_total counter",
            f"mt_heal_cycles_total {st.cycles}",
        ]
    # the MRF queue keeps its own HealStats; fall back to the sweep's
    # (always-zero mrf fields) so the families stay present for
    # healer-only renders
    mst = mrf.stats if mrf is not None else \
        (healer.stats if healer is not None else None)
    if mst is not None:
        lines += [
            "# TYPE mt_heal_mrf_queued_total counter",
            f"mt_heal_mrf_queued_total {mst.mrf_queued}",
            "# TYPE mt_heal_mrf_healed_total counter",
            f"mt_heal_mrf_healed_total {mst.mrf_healed}",
            "# TYPE mt_heal_mrf_dropped_total counter",
            f"mt_heal_mrf_dropped_total {mst.mrf_dropped}",
        ]
    return lines


def _fmt_rate(v: float) -> str:
    return f"{v:.3f}".rstrip("0").rstrip(".") or "0"


def _progress_gauges(prefix: str, progress) -> list[str]:
    """Rate gauges for one background plane's CycleProgress: live
    objects/s + bytes/s (last completed cycle's when idle) and an
    in-cycle flag — the `mc admin scanner status` rate columns."""
    ops, bps = progress.rates()
    return [
        f"# TYPE {prefix}_objects_per_second gauge",
        f"{prefix}_objects_per_second {_fmt_rate(ops)}",
        f"# TYPE {prefix}_bytes_per_second gauge",
        f"{prefix}_bytes_per_second {_fmt_rate(bps)}",
        f"# TYPE {prefix}_cycle_active gauge",
        f"{prefix}_cycle_active {1 if progress.active else 0}",
    ]


def _scanner_gauges(crawler) -> list[str]:
    prog = crawler.progress
    n_objects = prog.objects if prog.active \
        else prog.last.get("objects", 0)
    lines = [
        "# TYPE mt_scanner_cycles_total counter",
        f"mt_scanner_cycles_total {crawler.cycles}",
        "# TYPE mt_scanner_cycle_objects gauge",
        f"mt_scanner_cycle_objects {n_objects}",
    ]
    lines += _progress_gauges("mt_scanner", crawler.progress)
    return lines


def _replication_gauges(replication) -> list[str]:
    """ReplStats + BandwidthMonitor, scrape-visible (the stats existed
    since the replication PR but only the JSON admin routes saw them)."""
    st = replication.stats
    lines = [
        "# TYPE mt_replication_queued_total counter",
        f"mt_replication_queued_total {st.queued}",
        "# TYPE mt_replication_objects_total counter",
        f"mt_replication_objects_total {st.replicated}",
        "# TYPE mt_replication_bytes_total counter",
        f"mt_replication_bytes_total {st.replica_bytes}",
        "# TYPE mt_replication_failed_total counter",
        f"mt_replication_failed_total {st.failed}",
        "# TYPE mt_replication_deletes_total counter",
        f"mt_replication_deletes_total {st.deletes_replicated}",
        "# TYPE mt_replication_pending gauge",
        f"mt_replication_pending {replication._q.qsize()}",
    ]
    lines += _progress_gauges("mt_replication", replication.progress)
    report = replication.monitor.report()
    if report:
        lines += [
            "# TYPE mt_bucket_bandwidth_limit_bytes_per_second gauge",
            "# TYPE mt_bucket_bandwidth_moved_bytes_total counter",
        ]
        for b in sorted(report):
            r = report[b]
            bl = _fmt_labels((("bucket", b),))
            lines.append(
                "mt_bucket_bandwidth_limit_bytes_per_second"
                f"{bl} {r['limitInBytesPerSecond']}")
            lines.append(
                "mt_bucket_bandwidth_moved_bytes_total"
                f"{bl} {r['totalBytesMoved']}")
    return lines


def _rebalance_metrics(rebalancer) -> list[str]:
    """Rebalance-plane families (background/rebalance.py): lifetime
    move counters plus the live cycle's rate gauges — the drain/expand
    progress an operator watches during a topology change."""
    st = rebalancer.stats
    lines = [
        "# TYPE mt_rebalance_moved_objects_total counter",
        f"mt_rebalance_moved_objects_total {st.moved_objects}",
        "# TYPE mt_rebalance_moved_bytes_total counter",
        f"mt_rebalance_moved_bytes_total {st.moved_bytes}",
        "# TYPE mt_rebalance_failed_total counter",
        f"mt_rebalance_failed_total {st.failed}",
        "# TYPE mt_rebalance_cycles_total counter",
        f"mt_rebalance_cycles_total {st.cycles}",
    ]
    lines += _progress_gauges("mt_rebalance", rebalancer.progress)
    return lines


def _egress_metrics(egress) -> list[str]:
    """Telemetry-egress delivery families from the live targets'
    counters + state machines (obs/egress.py).  Everything is labelled
    ``{target_type, target}``; an empty registry emits nothing, so the
    scrape of an egress-less server carries no ``mt_target_*`` family
    at all."""
    targets = egress.targets()
    if not targets:
        return []
    stats = [(t, t.status()) for t in targets]

    def lbl(st) -> tuple:
        return (("target", st["target"]), ("target_type", st["type"]))

    lines: list[str] = []
    for fam, key, kind in (
            ("mt_target_sent_total", "sent", "counter"),
            ("mt_target_failed_total", "failed", "counter"),
            ("mt_target_dropped_total", "dropped", "counter"),
            ("mt_target_dead_letter_total", "deadLettered", "counter"),
            ("mt_target_queue_length", "queued", "gauge"),
            ("mt_target_store_length", "stored", "gauge"),
            ("mt_target_online", "online", "gauge")):
        lines.append(f"# TYPE {fam} {kind}")
        for _, st in stats:
            v = int(st[key]) if key == "online" else st[key]
            lines.append(f"{fam}{_fmt_labels(lbl(st))} {v}")
    lines.append("# TYPE mt_target_delivery_seconds histogram")
    for t, st in stats:
        buckets, counts, total = t.delivery_hist()
        labels = lbl(st)
        for i, ub in enumerate(buckets):
            le = 'le="%g"' % ub
            lines.append("mt_target_delivery_seconds_bucket"
                         f"{_fmt_labels(labels, le)} {counts[i]}")
        le_inf = 'le="+Inf"'
        lines.append("mt_target_delivery_seconds_bucket"
                     f"{_fmt_labels(labels, le_inf)}"
                     f" {counts[len(buckets)]}")
        lines.append("mt_target_delivery_seconds_sum"
                     f"{_fmt_labels(labels)} {_fmt_value(total)}")
        lines.append("mt_target_delivery_seconds_count"
                     f"{_fmt_labels(labels)} {counts[len(buckets)]}")
    return lines


def _disk_lastminute_gauges(layer, config=None) -> list[str]:
    """Per-drive last-minute latency families from the drives' rolling
    windows (cmd/last-minute.go role), plus the slow-drive flag —
    computed at scrape time, a slow drive is FLAGGED never ejected."""
    from ..obs.lastminute import drive_windows
    from ..storage.health import slow_drive_knobs, slow_drives_for_layer
    disks = _collect_disks(layer)
    wins = drive_windows(disks)
    if not wins:
        return []
    lines = [
        "# TYPE mt_node_disk_latency_ops gauge",
        "# TYPE mt_node_disk_latency_ns gauge",
        "# TYPE mt_node_disk_latency_avg_ns gauge",
        "# TYPE mt_node_disk_latency_bytes gauge",
    ]
    for drive in sorted(wins):
        for op, (c, t, b) in sorted(wins[drive].totals().items()):
            lbl = _fmt_labels((("drive", drive), ("op", op)))
            lines.append(f"mt_node_disk_latency_ops{lbl} {c}")
            lines.append(f"mt_node_disk_latency_ns{lbl} {t}")
            lines.append(f"mt_node_disk_latency_avg_ns{lbl}"
                         f" {t // max(c, 1)}")
            lines.append(f"mt_node_disk_latency_bytes{lbl} {b}")
    multiple, min_samples = slow_drive_knobs(config)
    verdicts = slow_drives_for_layer(layer, multiple=multiple,
                                     min_samples=min_samples)
    if verdicts:
        lines += ["# TYPE mt_node_disk_latency_p50_ns gauge",
                  "# TYPE mt_node_disk_latency_p99_ns gauge",
                  "# TYPE mt_node_disk_slow gauge"]
        for drive in sorted(verdicts):
            v = verdicts[drive]
            dl = _fmt_labels((("drive", drive),))
            lines.append(f"mt_node_disk_latency_p50_ns{dl}"
                         f" {v['p50_ns']}")
            lines.append(f"mt_node_disk_latency_p99_ns{dl}"
                         f" {wins[drive].p99_all() if drive in wins else 0}")
            lines.append(f"mt_node_disk_slow{dl}"
                         f" {1 if v['slow'] else 0}")
    return lines


def _put_pipeline_gauges(layer) -> list[str]:
    """Pipelined-PUT plane families (storage/writers.py): per-drive
    writer queue depth, enqueue stalls and completed ops, plus the
    last streaming PUT's overlap efficiency — critical-path seconds /
    wall seconds, so 1.0 means the pipeline hid everything but the
    slowest stage and ~max(stage)/sum(stages) means it degenerated to
    serial.  Computed at scrape time from the live plane; a layer
    whose plane never carried an op emits nothing (idle contract)."""
    from ..objectlayer.metacache import leaf_layers_of
    drives: list[tuple[str, dict]] = []
    effs: list[tuple[int, dict]] = []
    for si, leaf in enumerate(leaf_layers_of(layer)):
        plane = getattr(leaf, "_write_plane", None)
        if plane is None or not plane.used:
            continue
        drives += sorted(plane.stats().items())
        ps = getattr(leaf, "_pipe_stats", None)
        if ps and ps.get("wall_s"):
            effs.append((si, ps))
    lines: list[str] = []
    if drives:
        lines += ["# TYPE mt_put_pipeline_queue_depth gauge",
                  "# TYPE mt_put_pipeline_enqueue_stalls_total counter",
                  "# TYPE mt_put_pipeline_writes_total counter"]
        for ep, st in drives:
            lbl = _fmt_labels((("drive", ep),))
            lines.append(f"mt_put_pipeline_queue_depth{lbl}"
                         f" {st['queue_depth']}")
            lines.append(f"mt_put_pipeline_enqueue_stalls_total{lbl}"
                         f" {st['stalls']}")
            lines.append(f"mt_put_pipeline_writes_total{lbl}"
                         f" {st['ops']}")
    if effs:
        lines += ["# TYPE mt_put_pipeline_overlap_efficiency gauge",
                  "# TYPE mt_put_pipeline_batch_wall_seconds gauge"]
        for si, ps in effs:
            lbl = _fmt_labels((("set", str(si)),))
            lines.append(f"mt_put_pipeline_overlap_efficiency{lbl}"
                         f" {_fmt_value(ps['overlap_efficiency'])}")
            batches = max(1, ps.get("batches", 1))
            lines.append(f"mt_put_pipeline_batch_wall_seconds{lbl}"
                         f" {_fmt_value(ps['wall_s'] / batches)}")
    return lines


def _hot_read_gauges(layer) -> list[str]:
    """Hot-read plane families (objectlayer/hotread.py): resident
    cache bytes/entries summed over the layer's erasure sets at scrape
    time.  The event counters (mt_cache_{hits,misses,...}_total,
    mt_singleflight_*) are plain process counters ticked on the serve
    path.  Idle contract: a layer whose planes never served a read
    emits no family at all."""
    from ..objectlayer.metacache import leaf_layers_of
    entries = nbytes = 0
    used = False
    for leaf in leaf_layers_of(layer):
        plane = getattr(leaf, "hotread", None)
        if plane is None or not plane.used:
            continue
        used = True
        st = plane.cache.stats()
        entries += st["entries"]
        nbytes += st["bytes"]
    if not used:
        return []
    return ["# TYPE mt_cache_entries gauge",
            f"mt_cache_entries {entries}",
            "# TYPE mt_cache_bytes gauge",
            f"mt_cache_bytes {nbytes}"]


def _codec_batch_gauges() -> list[str]:
    """Live queued-block depth of the cross-request codec batcher
    (parallel/batcher.py), per op.  Idle contract: a process whose
    batcher never dispatched (or shed) emits no family at all."""
    from ..parallel import batcher
    b = batcher.GLOBAL
    if not b.started():
        return []
    depths = b.queue_depths()
    lines = ["# TYPE mt_codec_batch_queue_depth gauge"]
    for op in sorted(set(depths) | {"encode", "decode",
                                    "reconstruct"}):
        lbl = _fmt_labels((("op", op),))
        lines.append(f"mt_codec_batch_queue_depth{lbl}"
                     f" {depths.get(op, 0)}")
    return lines


def _locktrace_gauges() -> list[str]:
    """Lock-order detector families (utils/locktrace.py): recorded
    order-graph edges, detected cycles (potential AB/BA deadlocks),
    and long holds under contention.  Idle contract: tracing off (the
    default) or an empty graph emits no families at all."""
    from ..utils import locktrace
    return locktrace.render_metrics()


def _tls_gauges() -> list[str]:
    """TLS plane families (secure/certs.py): per-certificate seconds
    to expiry from every live CertManager.  The handshake and reload
    counters are plain process counters ticked on the TLS paths.  Idle
    contract: a process that never constructed a cert manager emits no
    mt_tls_* family at all."""
    from ..secure.certs import render_metrics
    return render_metrics()


def _memgov_gauges() -> list[str]:
    """Node memory-governor families (utils/memgov.py): configured
    watermark, outstanding charges per kind, and the process peak.
    Idle contract: an unconfigured governor that never took a charge
    (and never shed) emits no family at all.  ``mt_mem_shed_total``
    is a plain process counter ticked at shed time."""
    from ..utils.memgov import GOVERNOR
    if not GOVERNOR.touched:
        return []
    st = GOVERNOR.stats()
    lines = ["# TYPE mt_mem_limit_bytes gauge",
             f"mt_mem_limit_bytes {st['limit_bytes']}",
             "# TYPE mt_mem_peak_bytes gauge",
             f"mt_mem_peak_bytes {st['peak_bytes']}",
             "# TYPE mt_mem_inuse_bytes gauge"]
    inuse = st["inuse"]
    for kind in sorted(set(inuse) | {"select", "listing", "multipart",
                                     "cache", "pipeline"}):
        lbl = _fmt_labels((("kind", kind),))
        lines.append(f"mt_mem_inuse_bytes{lbl} {inuse.get(kind, 0)}")
    return lines


def _flight_gauges(flightrec) -> list[str]:
    """Flight-recorder families (obs/flightrec.py): ring depths and
    lifetime record counters from the server's recorder, computed at
    scrape time.  Idle contract: a recorder that never recorded a
    request emits no family at all.  ``mt_forensic_dumps_total`` (the
    bundle counter) is a plain process counter ticked at trigger
    time."""
    st = flightrec.stats()
    if not st["recordsTotal"]:
        return []
    lines = ["# TYPE mt_flight_ring_depth gauge"]
    for ring in ("requests", "errors", "snapshots"):
        lbl = _fmt_labels((("ring", ring),))
        lines.append(f"mt_flight_ring_depth{lbl} {st[ring]}")
    lines += [
        "# TYPE mt_flight_records_total counter",
        f"mt_flight_records_total {st['recordsTotal']}",
        "# TYPE mt_flight_errors_total counter",
        f"mt_flight_errors_total {st['errorsTotal']}",
    ]
    return lines


def _s3_lastminute_gauges(api_stats) -> list[str]:
    """Per-S3-API last-minute families from the server's rolling
    windows (minio_s3_requests 1m rate role)."""
    totals = api_stats.totals()
    if not totals:
        return []
    lines = [
        "# TYPE mt_s3_api_last_minute_requests gauge",
        "# TYPE mt_s3_api_last_minute_avg_ns gauge",
        "# TYPE mt_s3_api_last_minute_p99_ns gauge",
        "# TYPE mt_s3_api_last_minute_bytes gauge",
    ]
    for api in sorted(totals):
        c, t, b = totals[api]
        al = _fmt_labels((("api", api),))
        w = api_stats.windows.get(api)
        lines.append(f"mt_s3_api_last_minute_requests{al} {c}")
        lines.append(f"mt_s3_api_last_minute_avg_ns{al}"
                     f" {t // max(c, 1)}")
        lines.append(f"mt_s3_api_last_minute_p99_ns{al}"
                     f" {w.p99() if w is not None else 0}")
        lines.append(f"mt_s3_api_last_minute_bytes{al} {b}")
    return lines


def _watchdog_metrics(watchdog) -> list[str]:
    """Watchdog alert + telemetry-history families, computed at scrape
    time from the engine's own state (obs/watchdog.py).  A server with
    watchdog.enable=off hands ``watchdog=None`` into render() and
    emits NONE of these families (the idle contract)."""
    st = watchdog.metrics_state()
    hist = st.get("history") or {}
    lines = [
        "# TYPE mt_history_series gauge",
        f"mt_history_series {hist.get('series', 0)}",
        "# TYPE mt_history_samples_total counter",
        f"mt_history_samples_total {hist.get('samplesTotal', 0)}",
    ]
    evals = st.get("evals") or {}
    if evals:
        lines.append("# TYPE mt_alert_evals_total counter")
        for rule in sorted(evals):
            rl = _fmt_labels((("rule", rule),))
            lines.append(f"mt_alert_evals_total{rl} {evals[rule]}")
    transitions = st.get("transitions") or {}
    if transitions:
        lines.append("# TYPE mt_alert_transitions_total counter")
        for rule, to in sorted(transitions):
            tl = _fmt_labels((("rule", rule), ("to", to)))
            lines.append(f"mt_alert_transitions_total{tl}"
                         f" {transitions[(rule, to)]}")
    firing = st.get("firing") or []
    if firing:
        lines.append("# TYPE mt_alert_firing gauge")
        for rule, subject in sorted(firing):
            fl = _fmt_labels((("rule", rule), ("subject", subject)))
            lines.append(f"mt_alert_firing{fl} 1")
    return lines


def _metering_gauges(metering) -> list[str]:
    """Workload attribution families, computed at scrape time from the
    bounded registry (obs/metering.py Metering.metrics_state).  A
    server with metering.enable=off hands ``metering=None`` into
    render() and emits NONE of these families (the idle contract).
    Label cardinality is bounded BY the registry — at most max_buckets
    bucket values and tenant_k tenant values plus the ``_other``
    overflow row; object keys never appear as labels at all."""
    st = metering.metrics_state()
    lines: list[str] = []
    brows = st.get("bucketRows") or []
    if brows:
        lines += ["# TYPE mt_bucket_requests_total counter",
                  "# TYPE mt_bucket_errors_total counter",
                  "# TYPE mt_bucket_rx_bytes_total counter",
                  "# TYPE mt_bucket_tx_bytes_total counter"]
        for bucket, api, requests, errors, rx, tx in brows:
            bl = _fmt_labels((("bucket", bucket), ("api", api)))
            lines.append(f"mt_bucket_requests_total{bl} {requests}")
            if errors:
                lines.append(f"mt_bucket_errors_total{bl} {errors}")
            if rx:
                lines.append(f"mt_bucket_rx_bytes_total{bl} {rx}")
            if tx:
                lines.append(f"mt_bucket_tx_bytes_total{bl} {tx}")
    trows = st.get("tenantRows") or []
    if trows:
        lines += ["# TYPE mt_tenant_requests_total counter",
                  "# TYPE mt_tenant_errors_total counter",
                  "# TYPE mt_tenant_rx_bytes_total counter",
                  "# TYPE mt_tenant_tx_bytes_total counter",
                  "# TYPE mt_tenant_last_minute_p50_ns gauge",
                  "# TYPE mt_tenant_last_minute_p99_ns gauge"]
        for tenant, requests, errors, rx, tx, p50, p99 in trows:
            tl = _fmt_labels((("tenant", tenant),))
            lines.append(f"mt_tenant_requests_total{tl} {requests}")
            lines.append(f"mt_tenant_errors_total{tl} {errors}")
            lines.append(f"mt_tenant_rx_bytes_total{tl} {rx}")
            lines.append(f"mt_tenant_tx_bytes_total{tl} {tx}")
            lines.append(f"mt_tenant_last_minute_p50_ns{tl} {p50}")
            lines.append(f"mt_tenant_last_minute_p99_ns{tl} {p99}")
    lines += [
        "# TYPE mt_metering_sketch_memory_bytes gauge",
        f"mt_metering_sketch_memory_bytes {st.get('memoryBytes', 0)}",
        "# TYPE mt_metering_decays_total counter",
        f"mt_metering_decays_total {st.get('decays', 0)}",
    ]
    return lines


def _collect_disks_with_set(layer):
    """(set_index, disk) pairs across every topology shape; the set
    index is global across pools.  The traversal itself lives with the
    storage layer (health.disks_by_set) — one walk, shared by the
    scrape and slow-drive detection, so they can never disagree about
    which drives exist."""
    from ..storage.health import disks_by_set
    return [(si, d) for si, dlist in enumerate(disks_by_set(layer))
            for d in dlist]


def _collect_disks(layer):
    return [d for _, d in _collect_disks_with_set(layer)]
