#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the deployment the cell's configuration names (child processes
under ``harness/serve.py``; this process never imports JAX), drives it
through the S3 front with the benchmark's own client processes, and prints
as its LAST stdout line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``checks``: every number the run compares, with its limit (also the last
lines on stderr).  Earlier lines (prefixed ``#``) say what the run did:
filesystem, set-up breakdown, sample counts, the generator's own overhead,
stage sums.

A configuration with ``down_drives`` loses those drives once the preload
has finished (``Deployment.fail_drives``): the warm-up and the window run
on the failed set, GETs have to be rebuilt by the device codec, and the
on-disk oracle checks what survives.

``--trace 0``: the cell's end-to-end metrics.  ``--trace 1``: its per-layer
metrics; a ``jax.profiler`` slice of at most 5 s is taken in the middle of
the window by the shim, in the process that holds the chip.

``--rehearse`` (never passed by the driver) runs the same code at tiny
sizes, accepts ``JAX_PLATFORMS=cpu`` and always prints ``"correct": false``.
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.harness import (manifest, oracle, readers, stats,  # noqa: E402
                                trace_reduce, traffic)
from benchmarks.harness.client import S3Conn  # noqa: E402
from benchmarks.harness.deploy import (KEY, SECRET, Deployment, Failed,  # noqa: E402
                                       check)

BUCKET = "bench"
WARMUP_QUIET_S = 3.0        # compile tally unchanged for this long
WARMUP_CAP_S = 60.0
WARMUP_PUTS_PER_CLIENT = 2  # each client has been round the PUT path
TRACE_SLICE_S = 5.0
READ_BACK = 32
WINDOW_COMPILES = {"name": "window_compiles", "reader": {
    "kind": "info", "path": "codec.device.compile.compiles"}}
VERIFY_THREADS = 8


def say(msg: str) -> None:
    print(f"# [{time.monotonic() - T_PROC:6.1f}s] {msg}", flush=True)


def fs_type(path: str) -> str:
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mnt, typ = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) >= len(best):
                    best, kind = mnt, typ
    except OSError:
        pass
    return f"{kind} (mount {best or '?'})"


class Clients:
    """The generator processes and the line protocol with them."""

    def __init__(self, mix: dict, seed: int, endpoints: list[str]):
        self.procs = []
        n = mix["client_procs"]
        for p in range(n):
            mine = [(c, endpoints[c % len(endpoints)])
                    for c in range(mix["clients"]) if c % n == p]
            proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "harness", "client.py")],
                cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True)
            proc.stdin.write(json.dumps({
                "seed": seed, "mix": mix, "bucket": BUCKET, "clients": mine,
                "access_key": KEY, "secret_key": SECRET}) + "\n")
            proc.stdin.flush()
            self.procs.append(proc)

    def _read(self, proc) -> dict:
        line = proc.stdout.readline()
        check(line, f"a generator process ended early "
              f"(exit code {proc.poll()})")
        return json.loads(line)

    def ready(self) -> None:
        for p in self.procs:
            self._read(p)

    def _tell(self, proc, cmd: dict) -> None:
        proc.stdin.write(json.dumps(cmd) + "\n")
        proc.stdin.flush()

    def ask(self, cmd: dict) -> list[dict]:
        for p in self.procs:
            self._tell(p, cmd)
        return [self._read(p) for p in self.procs]

    def connect(self, setup: dict) -> None:
        """Open every client's connection, or prove it still open: one
        process after the other and one connection at a time inside each,
        so the servers' listen queue (backlog 5) never overflows.  Adds
        the seconds it took and the attempts that had to be repeated."""
        t = time.monotonic()
        for p in self.procs:
            self._tell(p, {"cmd": "connect"})
            setup["connects_repeated"] = setup.get("connects_repeated", 0) \
                + self._read(p)["repeated"]
        setup["connect_s"] = setup.get("connect_s", 0.0) \
            + time.monotonic() - t

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                try:
                    f.close()
                except OSError:
                    pass


PUTS = {"family": "mt_s3_requests_api_total", "labels": {"api": "PutObject"}}
ENCODES = {"family": "mt_tpu_ops_total", "labels": {"op": "encode"}}
MATMULS = {"family": "mt_tpu_ops_total", "labels": {"op": "matmul"}}


def compile_tally(infos: list[dict]) -> int:
    """Backend compile requests so far, over all processes."""
    return sum(i["codec"]["device"]["compile"]["compiles"] for i in infos)


def warm_up(dep: Deployment, clients: int) -> dict:
    """The cell's own traffic is already running.  Returns once the
    servers' compile tally has been quiet for WARMUP_QUIET_S, every client
    has been round the PUT path (in the preload or here) and the md5
    ``auto`` probe (which hashes on the device) has chosen; capped at
    WARMUP_CAP_S."""
    t0 = time.monotonic()
    last, last_change = compile_tally(dep.info()), t0
    while True:
        time.sleep(1.0)
        now = time.monotonic()
        infos = dep.info()
        tally = compile_tally(infos)
        if tally != last:
            last, last_change = tally, now
        probing = any(i["codec"]["md5"].get("configured") == "auto"
                      and i["codec"]["md5"].get("auto_choice") is None
                      for i in infos)
        puts = readers.total(dep.scrape(), PUTS) or 0   # the preload's too
        quiet = now - last_change >= WARMUP_QUIET_S
        capped = now - t0 >= WARMUP_CAP_S
        if capped or (quiet and not probing
                      and puts >= WARMUP_PUTS_PER_CLIENT * clients):
            return {"seconds": now - t0, "capped": capped, "puts": puts,
                    "md5_probe_pending": probing}


def take_trace(dep: Deployment, t_start: float, seconds: float) -> None:
    """Ask every shim for a slice of ``seconds`` from ``t_start`` on; each
    times its own slice (two files would cost a second poll)."""
    time.sleep(max(0.0, t_start - time.monotonic()))
    for ctl in dep.ctls:
        tmp = os.path.join(ctl, "trace.start.tmp")
        with open(tmp, "w") as f:
            json.dump({"seconds": seconds}, f)
        os.replace(tmp, os.path.join(ctl, "trace.start"))


def collect_traces(dep: Deployment, timeout: float = 120.0) -> list[dict]:
    """Each traced process's trace.done, once it is written."""
    out = []
    deadline = time.monotonic() + timeout
    for ctl in dep.ctls:
        path = os.path.join(ctl, "trace.done")
        while not os.path.exists(path):
            check(time.monotonic() < deadline,
                  f"no trace.done in {ctl} after {timeout:.0f}s")
            time.sleep(0.1)
        with open(path) as f:
            done = json.load(f)
        check("error" not in done, f"profiler: {done.get('error')}")
        done["dir"] = os.path.join(ctl, "trace")
        out.append(done)
    return out


def verify_after(dep, cell, live: dict, bodies: traffic.BodyPool,
                 seed: int) -> tuple[dict, list[str]]:
    """HEAD every surviving key, read a seeded sample back whole, and check
    one object of each size on the drives.  ``wrong`` counts what each of
    the four checks found wrong."""
    problems: list[str] = []
    wrong = dict.fromkeys(("head_after", "read_back", "drives_holding",
                           "on_disk"), 0)
    keys = sorted(live)
    eps = dep.endpoints

    def head(chunk):
        bad = []
        conn = conns[chunk[0]]
        for key in chunk[1]:
            size, bidx = live[key]
            _, md5, _ = bodies.get(size, bidx)
            st, h, _, _, _ = conn.request("HEAD", f"/{BUCKET}/{key}")
            if not (st == 200 and int(h.get("content-length", -1)) == size
                    and h.get("etag", "").strip('"') == md5):
                bad.append(f"HEAD {key} after the window: HTTP {st}, length "
                           f"{h.get('content-length')}, ETag {h.get('etag')}")
        conn.close()
        return bad

    chunks = [(i, keys[i::VERIFY_THREADS])
              for i in range(min(VERIFY_THREADS, len(keys)))]
    conns = [S3Conn(eps[i % len(eps)], KEY, SECRET) for i, _ in chunks]
    for c in conns:         # one at a time, as the generator's (client.py)
        c.open(f"/{BUCKET}")
    with ThreadPoolExecutor(VERIFY_THREADS) as ex:
        for bad in ex.map(head, chunks):
            problems += bad
            wrong["head_after"] += len(bad)

    g = cell.config["guarantees"]
    k, m = cell.config["fixes"]["data_shards"], \
        cell.config["fixes"]["parity_shards"]
    sample = random.Random(seed).sample(keys, min(READ_BACK, len(keys)))
    conn = S3Conn(eps[-1], KEY, SECRET)   # not the endpoint most keys used
    for key in sample:
        size, bidx = live[key]
        body, md5, _ = bodies.get(size, bidx)
        st, h, data, _, _ = conn.request("GET", f"/{BUCKET}/{key}")
        if not (st == 200 and data == body
                and h.get("etag", "").strip('"') == md5):
            problems.append(f"read-back {key}: HTTP {st}, {len(data)} bytes")
            wrong["read_back"] += 1
        held = sum(os.path.exists(os.path.join(d, BUCKET, key, "xl.meta"))
                   for d in dep.dirs)
        if held != g["shards_expected_on_healthy_drives"]:
            problems.append(f"{key}: on {held} drives, "
                            f"{g['shards_expected_on_healthy_drives']} "
                            f"expected")
            wrong["drives_holding"] += 1
    conn.close()

    on_disk = {}
    by_size: dict = {}
    for key in keys:
        by_size.setdefault(live[key][0], key)
    for size, key in sorted(by_size.items()):
        body = bodies.get(*live[key])[0]
        try:
            on_disk[key] = oracle.verify_on_disk(
                dep.dirs, BUCKET, key, body, k, m,
                g["shards_expected_on_healthy_drives"],
                tuple(cell.config.get("down_drives", ())))
        except Failed as e:
            problems.append(f"on-disk oracle: {e}")
            wrong["on_disk"] += 1
    return ({"head_checked": len(keys), "read_back": len(sample),
             "on_disk": on_disk, "wrong": wrong}, problems)


def device_of(infos: list[dict]) -> dict:
    devs = [i["codec"]["device"] for i in infos]
    check(all(devs), "a server reports no device: its codec resolved to "
          f"{[i['codec']['backends'] for i in infos]}")
    kinds = {(d["platform"], d["device_kind"]) for d in devs}
    check(len(kinds) == 1, f"servers report different devices: {kinds}")
    peaks = [x["peak_bytes_in_use"] for d in devs for x in d["devices"]
             if x.get("peak_bytes_in_use") is not None]
    return {"platform": devs[0]["platform"], "kind": devs[0]["device_kind"],
            "count": sum(d["device_count"] for d in devs),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def run(args, cell: manifest.Cell, work: str, logs: dict) -> dict:
    mix = traffic.effective(cell.traffic, args.rehearse)
    seconds = float(args.seconds)
    dep = Deployment(cell.config, work, bool(args.trace), args.rehearse)
    down = cell.config.get("down_drives", [])
    clients = None
    setup: dict = {}
    try:
        oracle.host_hash_ready()
        t = time.monotonic()
        dep.start()
        clients = Clients(mix, args.seed, dep.endpoints)
        bodies = traffic.BodyPool(args.seed, mix["sizes"],
                                  mix["bodies_per_size"])
        dep.wait_live(900)
        clients.ready()
        setup["start_to_live_s"] = time.monotonic() - t

        infos = dep.info()
        dev = device_of(infos)
        for i in infos:
            d = i["codec"]["device"]
            say(f"server: backends={i['codec']['backends']} platform="
                f"{d['platform']} kind={d['device_kind']!r} devices="
                f"{d['device_count']} kernels={d['kernels']} compile_cache="
                f"{d['compile_cache']}")
        if not args.rehearse:
            check(dev["platform"] == "tpu", f"the servers run on platform "
                  f"{dev['platform']!r}, not on a TPU")
            check(dev["count"] == cell.chips, f"the servers hold "
                  f"{dev['count']} chips, the cell asks for {cell.chips}")
        c0 = [i["codec"]["device"]["compile"] for i in infos]

        t = time.monotonic()
        conn = S3Conn(dep.endpoints[0], KEY, SECRET)
        st = conn.request("PUT", f"/{BUCKET}")[0]
        conn.close()
        check(st == 200, f"make bucket: HTTP {st}")
        clients.connect(setup)
        plan = traffic.preload_plan(mix, args.seed)
        got = clients.ask({"cmd": "preload", "plan": plan})
        bad = sum(g["errors"] for g in got)
        check(not bad, f"preload: {bad} of {len(plan)} PUTs failed: "
              f"{[e for g in got for e in g['error_samples']][:5]}")
        setup["preload_s"] = time.monotonic() - t
        setup["preload_objects"] = len(plan)
        if down:
            t = time.monotonic()
            dep.fail_drives()
            setup["fault_s"] = time.monotonic() - t
            say(f"fault: drives {down} failed {t - T_PROC:.3f} s after "
                f"start")

        # a client that finished its preload early has been idle since
        clients.connect(setup)
        clients.ask({"cmd": "start"})
        setup["warm_up"] = warm_up(dep, mix["clients"])
        infos0 = dep.info()
        c1 = [i["codec"]["device"]["compile"] for i in infos0]
        setup["compile"] = {
            k: round(sum(b[k] - a[k] for a, b in zip(c0, c1)), 3)
            for k in ("compiles", "cache_hits", "cache_writes",
                      "trace_seconds", "lower_seconds", "compile_seconds")}
        setup["compiles_before_live"] = sum(a["compiles"] for a in c0)

        # -- the measured window: traffic keeps running through its edges
        scrape0 = dep.scrape()
        t0 = time.monotonic()
        setup_s = t0 - T_PROC
        say(f"setup {json.dumps(setup)} setup_s={setup_s:.3f}")
        if args.trace:
            sl = min(TRACE_SLICE_S, seconds / 2)
            take_trace(dep, t0 + (seconds - sl) / 2, sl)
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        scrape1 = dep.scrape()
        infos1 = dep.info()

        outs = clients.ask({"cmd": "stop"})
        clients.close()
        clients = None
        records = [r for o in outs for r in o["records"]]
        errors = [e for o in outs for e in o["errors"]]
        mismatches = [e for o in outs for e in o["mismatches"]]
        live = {k: tuple(v) for o in outs for k, v in o["live"].items()}
        window = stats.in_window(records, t0, t1)
        span = t1 - t0
        outside = sum(1 for r in records if not r[stats.OK]
                      and not t0 <= r[stats.T1] <= t1)

        t = time.monotonic()
        traces = collect_traces(dep) if args.trace else []
        t_traces = time.monotonic() - t
        verified, problems = verify_after(dep, cell, live, bodies, args.seed)
        problems += mismatches
        if outside:     # inside the window they are counted as ``failed``
            problems.append(f"{outside} operations failed in the warm-up "
                            f"or after the window: {errors[:3]}")
        dev = device_of(infos1)
        say(f"tail: clients stopped {t - t1:.1f}s after the window, traces "
            f"written {t_traces:.1f}s, verified {verified['head_checked']} "
            f"keys in {time.monotonic() - t - t_traces:.1f}s")
    finally:
        if clients is not None:
            clients.close()
        t = time.monotonic()
        dep.stop()
        say(f"servers stopped in {time.monotonic() - t:.1f}s")
        logs.update(dep.log_tails())

    # -- everything below runs with the servers gone ----------------------
    attempted = len(window)
    failed = sum(1 for r in window if not r[stats.OK])
    puts_in_window = sum(1 for r in window
                         if r[stats.OP] == "PUT" and r[stats.OK])
    enc = readers.delta({"scrape0": scrape0, "scrape1": scrape1},
                        ENCODES) or 0
    if puts_in_window and enc <= 0:
        problems.append(f"{puts_in_window} PUTs completed in the window but "
                        f"mt_tpu_ops_total{{op=encode}} rose by {enc}: the "
                        f"device codec did not encode them")
    gets = sum(1 for r in window if r[stats.OP] == "GET" and r[stats.OK])
    if down:
        dec = readers.delta({"scrape0": scrape0, "scrape1": scrape1},
                            MATMULS) or 0
        say(f"decode: {dec:.0f} matmul dispatches for {gets} GETs in the "
            f"window, drives {down} failed")
        if gets and dec <= 0:
            problems.append(f"{gets} GETs completed in the window on a set "
                            f"with drives {down} failed but "
                            f"mt_tpu_ops_total{{op=matmul}} rose by {dec}: "
                            f"the device codec rebuilt nothing")
    # every number the run compares, beside its limit
    checks = {"wrong_in_window": (len(mismatches), "at_most", 0),
              **{f"wrong_{n}": (v, "at_most", 0)
                 for n, v in verified["wrong"].items()},
              "failed_outside_window": (outside, "at_most", 0)}
    if puts_in_window:
        checks["encode_dispatches"] = (enc, "at_least", 1)
    if down and gets:
        checks["matmul_dispatches"] = (dec, "at_least", 1)
    over = stats.generator_overhead_share(records, t0, t1)
    say(f"window {span:.3f}s: attempted={attempted} failed={failed} "
        + " ".join(f"{op}={sum(1 for r in window if r[stats.OP] == op)}"
                   for op in traffic.OPS)
        + f" delete_as_put={sum(o['delete_as_put'] for o in outs)} "
        f"generator_overhead_share={over:.4f} window_compiles="
        f"{readers.read(WINDOW_COMPILES, {'info0': infos0, 'info1': infos1})}")
    for e in (errors + problems)[:10]:
        say(f"problem: {e}")
    # per-type medians, whole window and by thirds of it: recorded on
    # this line only, they decide nothing (PERF.md section 6)
    for op in traffic.OPS:
        lat = stats.latencies_ms(window, op)
        if lat:
            thirds = [stats.percentile(stats.latencies_ms(stats.in_window(
                window, t0 + i * span / 3, t0 + (i + 1) * span / 3), op), 50)
                for i in range(3)]
            say(f"latency {op}: n={len(lat)} p50="
                f"{stats.percentile(lat, 50):.1f} ms, by thirds "
                f"{[t and round(t, 1) for t in thirds]}")

    ctx = {"scrape0": scrape0, "scrape1": scrape1, "info0": infos0,
           "info1": infos1, "window": window, "seconds": span}
    breakdown = None
    if args.trace:
        sums = []
        for tr in traces:
            path = trace_reduce.find_xplane(tr["dir"])
            check(path, f"the profiler left no .xplane.pb in {tr['dir']}")
            sums.append(trace_reduce.summarize(
                trace_reduce.load(path),
                (tr["unix_ns_start"], tr["unix_ns_stop"]),
                rehearse=args.rehearse))
        check(all(sums), "no operation ran on the device in the traced "
              "slice")
        s0 = max(tr["t_start"] for tr in traces)
        s1 = min(tr["t_stop"] for tr in traces)
        with open(os.path.join(HERE, "harness", "peaks.json")) as f:
            peaks = json.load(f)
        if not args.rehearse:
            check(dev["kind"] in peaks, f"device kind {dev['kind']!r} is "
                  f"not in peaks.json")
        ctx["trace"] = {
            "summaries": sums,
            "puts": [r[stats.SIZE] for r in stats.in_window(records, s0, s1)
                     if r[stats.OP] == "PUT" and r[stats.OK]],
            "k": cell.config["fixes"]["data_shards"],
            "m": cell.config["fixes"]["parity_shards"],
            "peaks": peaks.get(dev["kind"], peaks["TPU v5 lite"])}
        chips = sum(s["chips"] for s in sums)
        dev["busy_s"] = sum(s["busy_s_total"] for s in sums) / chips
        dev["window_s"] = sum(s["window_s"] * s["chips"]
                              for s in sums) / chips

        def merged(key):
            acc: dict = {}
            for s in sums:
                for row in s[key]:
                    acc[row[0]] = acc.get(row[0], 0.0) \
                        + row[1] * s["chips"] / chips
            return sorted(([k, v] for k, v in acc.items()),
                          key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": merged("device_ops"),
                     "idle_gaps": merged("idle_gaps")}
        say(f"trace slice: {len(ctx['trace']['puts'])} PUTs, programs "
            f"{json.dumps([s['programs'] for s in sums])}")

    metrics = {}
    if args.trace:
        for spec in cell.per_layer:
            v = readers.read(spec, ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        rate = stats.rate_per_s(window, span)
        say(f"traced run: ops_per_s={rate:.4f} (compare the untraced run: "
            f"the difference is the tracing overhead)")
    else:
        for spec in cell.end_to_end:
            v, n = stats.evaluate(spec, window, span, setup_s)
            say(f"metric {spec['name']}: {n} samples"
                + ("" if v is not None else " - too few, left out"))
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}

    # attribution, not a partition: stages that also run off the request
    # thread are observed into the same family (s3/server.py:1518-1525)
    put_lat = stats.latencies_ms(window, "PUT")
    stage_sum = readers.read({"name": "_", "reader": {
        "kind": "stage", "api": "PutObject", "stages": None,
        "per": "PutObject", "scale": 1000}}, ctx)
    if put_lat and stage_sum is not None:
        say(f"PutObject: sum of server stages {stage_sum:.1f} ms per PUT; "
            f"client mean latency {sum(put_lat) / len(put_lat):.1f} ms")

    correct = not problems and not args.rehearse
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, rel: lim}
                        for n, (v, rel, lim) in checks.items()}
    if args.rehearse and problems:
        raise Failed(f"rehearsal found problems: {problems[:3]}")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, CPU accepted, correct is always false")
    args = ap.parse_args()
    # ended from outside (a time limit): leave through the finally blocks,
    # so that no server or generator outlives the run
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    logs: dict = {}     # the servers' last lines, printed if the run fails
    work = None
    try:
        cell = manifest.Cell(manifest.load_manifest(), args.workload)
        check(os.environ.get("MT_FSYNC", "1") != "0",
              "MT_FSYNC=0 in the environment: the configurations promise "
              "fsync before the acknowledgement")
        work = tempfile.mkdtemp(prefix="mtbench_")
        say(f"cell {cell.name} seed {args.seed}: drives under {work}, "
            f"filesystem {fs_type(os.path.realpath(work))}, fsync on")
        result = run(args, cell, work, logs)
        check("jax" not in sys.modules, "the harness imported jax")
    except Exception as e:  # noqa: BLE001 — any error is a failed run: say why, print no result
        for name, tail in logs.items():
            say(f"--- {name} (tail) ---\n{tail}")
        if not isinstance(e, (Failed, manifest.ManifestError)):
            traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
        return 1
    finally:
        if work:
            shutil.rmtree(work, ignore_errors=True)
    for name, c in result["checks"].items():
        print(f"check {name}: " + ", ".join(f"{k} {v}" for k, v in c.items()),
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
