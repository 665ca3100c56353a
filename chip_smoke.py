#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Starts the real entry point as ONE child process,

    python -m minio_tpu server <16 drive dirs> --backend tpu

(one 12+4 erasure set, default 10 MiB block, default fsync and config) and
drives it over HTTP with the bundled S3Client / AdminClient:

  1. put       24 x 10 MiB, one 192 MiB single PUT (several stream batches),
               one 256 MiB multipart in 16 MiB parts, 64 x 256 KiB (packed
               band), 16 x 64 KiB (inline) — data from --seed
  2. get       everything back; body md5 vs hashlib and vs the ETag
  3. on_disk   for one object per size class: read the shard files of all
               16 drives straight off the disk, check every 32-byte frame
               digest with the HOST HighwayHash and recompute parity from
               the data shards with ops/gf8_ref — byte-identical to what
               the device wrote, by code that never touches the device
  4. degraded  delete several objects' directories on 2 drives (one object
               on 4 = m); full and ranged GETs still correct and the
               server's mt_tpu_ops_total{op=decode|matmul} rose
  5. heal      admin heal -> after_ok == 16, healed shards byte-equal to
               the ones deleted

The parent never imports JAX (a chip belongs to one process: the server).
Everything said about the device — platform, device_kind, count, resolved
backend, kernel form, compile tallies, compile-cache entries, md5 ``auto``
choice, native libraries — is read back from the server's admin ``info``.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only if every phase passed on a TPU.  ``--tiny`` runs the same phases at a
small size and is the only mode that accepts another platform
(``JAX_PLATFORMS=cpu python chip_smoke.py --tiny`` is the pre-flight on a
host without a chip); its last line carries ``"tiny": true``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --backend mesh  # one server process over every chip
    python chip_smoke.py --nodes 4       # four one-chip node processes
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

try:
    import numpy as np
    from minio_tpu.admin.client import AdminClient
    from minio_tpu.hashing import highwayhash
    from minio_tpu.ops import gf8, gf8_native, gf8_ref
    from minio_tpu.s3.client import S3Client
    from minio_tpu.storage.xl_meta import XLMeta
except ImportError as e:
    sys.exit(f"chip_smoke: needs the minio_tpu package beside it ({e})")

MiB = 1 << 20
KEY, SECRET = "minioadmin", "minioadmin"
BUCKET = "smoke"
DRIVES, K, M = 16, 12, 4


class Failed(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


class _Client(S3Client):
    """S3Client with room for a cold compile inside one request."""

    def _connect(self, u):
        return http.client.HTTPConnection(u.hostname, u.port, timeout=900)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def body_for(seed: int, idx: int, size: int) -> bytes:
    return np.random.default_rng([seed, idx]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


# -- the deployment ----------------------------------------------------------

def workload(tiny: bool) -> dict:
    """Size classes: key prefix -> (count, size).  Few distinct sizes on
    purpose: every new shard width is a new compile."""
    if tiny:
        return {"block_size": MiB, "stream_batch": 4 * MiB,
                "ten": (3, MiB), "stream": (1, 13 * MiB + 4321),
                "mp_part": 5 * MiB, "mp_parts": 3,
                "packed": (4, 256 * 1024), "inline": (2, 64 * 1024)}
    return {"block_size": None, "stream_batch": None,
            "ten": (24, 10 * MiB), "stream": (1, 192 * MiB),
            "mp_part": 16 * MiB, "mp_parts": 16,
            "packed": (64, 256 * 1024), "inline": (16, 64 * 1024)}


def start_servers(args, work: str, wl: dict):
    """One ``server`` child, or ``--nodes N`` ``node`` children pinned
    one chip each by their environment.  Returns (procs, their S3
    endpoints, dirs, log paths); traffic goes to the first endpoint."""
    dirs = [os.path.join(work, f"d{i:02d}") for i in range(DRIVES)]
    env = dict(os.environ)
    if wl["stream_batch"]:
        env["MT_STREAM_BATCH"] = str(wl["stream_batch"])
    extra = ["--backend", args.backend]
    procs, logs, endpoints = [], [], []

    def spawn(cmd, env, tag):
        path = os.path.join(work, f"{tag}.log")
        logs.append(path)
        procs.append(subprocess.Popen(
            cmd, cwd=HERE, env=env, stdout=open(path, "wb"),
            stderr=subprocess.STDOUT))

    if args.nodes <= 1:
        if wl["block_size"]:
            extra += ["--block-size", str(wl["block_size"])]
        endpoints.append(f"127.0.0.1:{free_port()}")
        spawn([sys.executable, "-m", "minio_tpu", "server", *dirs,
               "--address", endpoints[0], *extra], env, "server")
    else:
        per = DRIVES // args.nodes
        rpc = [free_port() for _ in range(args.nodes)]
        peers = [f"n{i}=127.0.0.1:{rpc[i]}="
                 + ",".join(dirs[i * per:(i + 1) * per])
                 for i in range(args.nodes)]
        for i in range(args.nodes):
            nenv = dict(env, MT_CLUSTER_SECRET="chip-smoke",
                        TPU_VISIBLE_CHIPS=str(i))
            # one chip per process is the launcher's business, not the
            # program's: each node sees exactly one device
            nenv.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
            nenv.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
            endpoints.append(f"127.0.0.1:{free_port()}")
            spawn([sys.executable, "-m", "minio_tpu", "node",
                   "--node-id", f"n{i}", "--address", endpoints[i],
                   "--set-drive-count", str(DRIVES), *extra, *peers],
                  nenv, f"node{i}")
    return procs, [f"http://{e}" for e in endpoints], dirs, logs


def stop_servers(procs, endpoints) -> None:
    """Every process this script started is gone when it returns: the
    admin stop first (a clean exit), then SIGTERM, then SIGKILL."""
    for p, ep in zip(procs, endpoints):
        if p.poll() is None:
            try:
                AdminClient(ep, KEY, SECRET).service_stop()
            except Exception:  # noqa: BLE001 — terminate() below covers it
                pass
    deadline = time.monotonic() + 15
    for sig in ("wait", "terminate", "kill"):
        for p in procs:
            if p.poll() is None and sig != "wait":
                getattr(p, sig)()
        for p in procs:
            try:
                p.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
        deadline = time.monotonic() + 15


def wait_live(procs, endpoint: str, timeout: float) -> None:
    u = endpoint.split("//")[1]
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for p in procs:
            check(p.poll() is None,
                  f"server process exited with code {p.returncode} "
                  f"before serving")
        try:
            c = http.client.HTTPConnection(u, timeout=5)
            c.request("GET", "/minio/health/live")
            if c.getresponse().status == 200:
                return
        except OSError:
            pass
        time.sleep(0.5)
    raise Failed(f"server not live after {timeout:.0f}s")


# -- reading the server back -------------------------------------------------

def codec_info(adm) -> dict:
    return adm.server_info()["codec"]


def scrape(endpoint: str, *families: str) -> dict:
    """{family: {(label values in label order): value}} from the
    server's Prometheus scrape."""
    c = http.client.HTTPConnection(endpoint.split("//")[1], timeout=60)
    c.request("GET", "/minio-tpu/metrics")
    out: dict = {f: {} for f in families}
    for line in c.getresponse().read().decode().splitlines():
        name, _, rest = line.partition("{")
        if name in out and rest:
            labels, val = rest.rsplit("} ", 1)
            key = tuple(p.split("=", 1)[1].strip('"')
                        for p in labels.split(","))
            out[name][key] = float(val)
    return out


def tpu_ops(endpoint: str) -> dict:
    """mt_tpu_ops_total by (backend, op)."""
    return scrape(endpoint, "mt_tpu_ops_total")["mt_tpu_ops_total"]


def where_the_time_went(endpoint: str) -> dict:
    """The server's own per-request stage clock and codec dispatch
    walls, summed over the run (host clock; seconds and counts)."""
    m = scrape(endpoint, "mt_s3_stage_seconds_sum",
               "mt_s3_stage_seconds_count", "mt_tpu_kernel_seconds_sum",
               "mt_tpu_kernel_seconds_count")
    out = {"s3_stage": {}, "codec_dispatch": {}}
    for (api, stage, vec), v in m["mt_s3_stage_seconds_sum"].items():
        n = m["mt_s3_stage_seconds_count"].get((api, stage, vec), 0)
        # serial stages of one API add up to its wall; async detail
        # overlaps it and is kept apart
        name = stage if vec == "serial" else f"{stage} (async)"
        out["s3_stage"].setdefault(api, {})[name] = \
            {"seconds": round(v, 3), "count": int(n)}
    for (backend, op), v in m["mt_tpu_kernel_seconds_sum"].items():
        n = m["mt_tpu_kernel_seconds_count"].get((backend, op), 0)
        out["codec_dispatch"][f"{op}[{backend}]"] = \
            {"seconds": round(v, 3), "count": int(n)}
    return out


# -- the on-disk oracle (host HighwayHash + gf8_ref; no device, no JAX) ------

def read_shards(dirs, key: str) -> dict:
    """shard index -> {"dir", "parts": {n: framed bytes}, "fi"} for every
    drive that holds the object, straight from the drive directories."""
    out = {}
    for d in dirs:
        mp = os.path.join(d, BUCKET, key, "xl.meta")
        if not os.path.exists(mp):
            continue
        with open(mp, "rb") as f:
            fi = XLMeta.load(f.read()).to_fileinfo(BUCKET, key)
        parts = {}
        for p in fi.parts:
            if fi.inline_data is not None:
                parts[p.number] = bytes(fi.inline_data)
            elif fi.seg:
                seg = os.path.join(d, ".mt.sys", "seg",
                                   f"seg.{fi.seg['sid']:08x}.dat")
                with open(seg, "rb") as f:
                    f.seek(fi.seg["off"])
                    parts[p.number] = f.read(fi.seg["len"])
            else:
                with open(os.path.join(d, BUCKET, key, fi.data_dir,
                                       f"part.{p.number}"), "rb") as f:
                    parts[p.number] = f.read()
        out[fi.erasure.index - 1] = {"dir": d, "parts": parts, "fi": fi}
    return out


def verify_on_disk(dirs, key: str, body: bytes) -> dict:
    """Every frame digest against the host HighwayHash; parity recomputed
    from the data shards by gf8_ref; data shards against the body."""
    shards = read_shards(dirs, key)
    check(len(shards) == DRIVES, f"{key}: {len(shards)}/{DRIVES} shards")
    fi = shards[0]["fi"]
    ec = fi.erasure
    check((ec.data_blocks, ec.parity_blocks) == (K, M),
          f"{key}: geometry {ec.data_blocks}+{ec.parity_blocks}")
    bs, ss = ec.block_size, ec.shard_size()
    frames = blocks = 0
    off = 0
    for part in fi.parts:
        nfull, tail = divmod(part.size, bs)
        tail_ss = gf8.ceil_frac(tail, K)
        want_len = nfull * (32 + ss) + ((32 + tail_ss) if tail else 0)
        rows = []
        for i in range(DRIVES):
            raw = np.frombuffer(shards[i]["parts"][part.number], np.uint8)
            check(raw.size == want_len,
                  f"{key} part {part.number} shard {i}: {raw.size} bytes "
                  f"on disk, {want_len} expected")
            bad = highwayhash.hh256_verify_framed(raw, ss)
            check(bad == 0, f"{key} part {part.number} shard {i}: host "
                  f"HighwayHash rejects frame {bad}")
            rows.append(raw)
            frames += nfull + (1 if tail else 0)
        framed = np.stack(rows)                        # (16, want_len)
        pbody = np.frombuffer(body, np.uint8)[off:off + part.size]
        for b in range(nfull + (1 if tail else 0)):
            n = ss if b < nfull else tail_ss
            base = b * (32 + ss) + 32
            stripe = framed[:, base:base + n]
            want = gf8_ref.encode_parity(
                np.ascontiguousarray(stripe[:K]), M)
            check(np.array_equal(want, stripe[K:]),
                  f"{key} part {part.number} block {b}: parity on disk "
                  f"differs from gf8_ref")
            blen = bs if b < nfull else tail
            check(np.array_equal(
                stripe[:K].reshape(-1)[:blen],
                pbody[b * bs:b * bs + blen]),
                f"{key} part {part.number} block {b}: data shards differ "
                f"from the body")
            blocks += 1
        off += part.size
    check(off == len(body), f"{key}: parts cover {off} of {len(body)}")
    return {"frames": frames, "blocks": blocks}


# -- phases ------------------------------------------------------------------

def run(args, report: dict) -> None:
    wl = workload(args.tiny)
    report.update(backend_requested=args.backend, nodes=args.nodes,
                  tiny=args.tiny, seed=args.seed, phases={})
    # the oracle is only an oracle if it is the native host code
    check(highwayhash._get_lib() is not None,
          "host HighwayHash is the pure-Python fallback (no C compiler?)")
    check(gf8_native.available(),
          "host gf8 is the numpy fallback (no C++ compiler?)")

    work = tempfile.mkdtemp(prefix="chip_smoke_", dir=args.workdir)
    procs, endpoints, logs = [], [], []
    try:
        t = time.monotonic()
        procs, endpoints, dirs, logs = start_servers(args, work, wl)
        endpoint = endpoints[0]
        wait_live(procs, endpoint, 600 if args.nodes <= 1 else 240)
        s3 = _Client(endpoint, KEY, SECRET)
        adm = AdminClient(endpoint, KEY, SECRET)
        adm._c = s3
        info0 = codec_info(adm)
        dev = info0["device"]
        log(f"server live: backends={info0['backends']} device="
            f"{dev and (dev['platform'], dev['device_kind'], dev['device_count'], dev['kernels'])}")
        check(info0["backends"] == [args.backend],
              f"server resolved backends {info0['backends']}, "
              f"asked for {args.backend}")
        check(dev is not None, "server reports no device")
        if not args.tiny:
            check(dev["platform"] == "tpu",
                  f"platform is {dev['platform']!r}, not 'tpu' (CPU is "
                  f"only accepted with --tiny)")
            check(dev["kernels"] == "pallas-mosaic",
                  f"kernels run as {dev['kernels']!r} on the chip path")
        report["device_at_start"] = dev
        report["phases"]["start"] = round(time.monotonic() - t, 2)

        bodies: dict[str, bytes] = {}
        etags: dict[str, str] = {}
        per_size: dict = {}

        def timed_put(cls: str, key: str, body: bytes, put) -> None:
            """PUT and, for the first object of a size class, the
            server's compile tally around it."""
            first = cls not in per_size
            c0 = codec_info(adm)["device"]["compile"] if first else None
            t0 = time.monotonic()
            etags[key] = put()
            dt = time.monotonic() - t0
            bodies[key] = body
            rec = per_size.setdefault(
                cls, {"bytes": len(body), "first_put_s": round(dt, 3),
                      "later_put_s": []})
            if first:
                c1 = codec_info(adm)["device"]["compile"]
                rec["first_put_compiles"] = c1["compiles"] - c0["compiles"]
                rec["first_put_cache_hits"] = \
                    c1["cache_hits"] - c0["cache_hits"]
                for stage in ("trace", "lower", "compile"):
                    rec[f"first_put_{stage}_s"] = round(
                        c1[f"{stage}_seconds"] - c0[f"{stage}_seconds"], 3)
                log(f"first {cls} PUT ({len(body)} B): {dt:.2f}s, "
                    f"{rec['first_put_compiles']} compiles "
                    f"({rec['first_put_cache_hits']} cache hits): trace "
                    f"{rec['first_put_trace_s']}s lower "
                    f"{rec['first_put_lower_s']}s compile "
                    f"{rec['first_put_compile_s']}s")
            else:
                rec["later_put_s"].append(round(dt, 3))

        # --nodes: PUTs go round the nodes, so every chip encodes
        clients = [s3] + [_Client(ep, KEY, SECRET) for ep in endpoints[1:]]

        def simple_put(key, body):
            r = clients[len(bodies) % len(clients)].put_object(
                BUCKET, key, body)
            return {k.lower(): v for k, v in r.headers.items()}[
                "etag"].strip('"')

        # 1. put ------------------------------------------------------
        t = time.monotonic()
        s3.make_bucket(BUCKET)
        idx = 0
        for cls in ("ten", "stream", "packed", "inline"):
            count, size = wl[cls]
            for i in range(count):
                key = f"{cls}/{i:03d}"
                body = body_for(args.seed, idx, size)
                idx += 1
                timed_put(cls, key, body,
                          lambda: simple_put(key, body))
        mp_key = "mp/000"
        mp_body = body_for(args.seed, idx, wl["mp_part"] * wl["mp_parts"])

        def multipart():
            uid = s3.create_multipart_upload(BUCKET, mp_key)
            parts = []
            for n in range(wl["mp_parts"]):
                chunk = mp_body[n * wl["mp_part"]:(n + 1) * wl["mp_part"]]
                parts.append((n + 1, s3.upload_part(
                    BUCKET, mp_key, uid, n + 1, chunk)))
            s3.complete_multipart_upload(BUCKET, mp_key, uid, parts)
            md5s = b"".join(hashlib.md5(
                mp_body[n * wl["mp_part"]:(n + 1) * wl["mp_part"]]
            ).digest() for n in range(wl["mp_parts"]))
            return f"{hashlib.md5(md5s).hexdigest()}-{wl['mp_parts']}"

        timed_put("mp", mp_key, mp_body, multipart)
        total = sum(len(b) for b in bodies.values())
        report["logical_bytes"] = total
        report["objects"] = len(bodies)
        report["phases"]["put"] = round(time.monotonic() - t, 2)
        log(f"put: {len(bodies)} objects, {total / MiB:.0f} MiB")

        # 2. get ------------------------------------------------------
        t = time.monotonic()
        for key, body in bodies.items():
            r = s3.get_object(BUCKET, key)
            got = hashlib.md5(r.body).hexdigest()
            check(r.body == body, f"GET {key}: body differs "
                  f"({len(r.body)} vs {len(body)} bytes)")
            etag = {k.lower(): v for k, v in r.headers.items()}[
                "etag"].strip('"')
            check(etag == etags[key], f"GET {key}: ETag {etag} != PUT's")
            if "-" not in etag:        # multipart ETags are not a body md5
                check(etag == got, f"GET {key}: ETag {etag} != md5 {got}")
        report["phases"]["get"] = round(time.monotonic() - t, 2)
        log("get: all bodies and ETags match")

        # 3. on-disk oracle ------------------------------------------
        t = time.monotonic()
        oracle = {}
        for key in ("ten/000", "stream/000", "mp/000", "packed/000",
                    "inline/000"):
            oracle[key] = verify_on_disk(dirs, key, bodies[key])
        report["on_disk"] = oracle
        report["phases"]["on_disk"] = round(time.monotonic() - t, 2)
        log(f"on_disk: {oracle}")

        # 4. degraded reads ------------------------------------------
        t = time.monotonic()
        # shard positions to lose: data AND parity, so GET must decode
        # and heal must rebuild both kinds
        victims = {"ten/001": (0, 5), "ten/002": (3, 13),
                   "ten/000": (1, 6, 12, 15),        # m = 4 lost
                   "stream/000": (2, 7), "mp/000": (4, 14),
                   "packed/001": (0, 9), "inline/001": (1, 11)}
        lost: dict = {}
        for key, idxs in victims.items():
            sh = read_shards(dirs, key)
            for i in idxs:
                lost[(key, i)] = sh[i]["parts"]
                shutil.rmtree(os.path.join(sh[i]["dir"], BUCKET, key))
        ops0 = tpu_ops(endpoint)
        for key in victims:
            body = bodies[key]
            r = s3.get_object(BUCKET, key)
            check(r.body == body, f"degraded GET {key}: body differs")
            a, b = len(body) // 3, len(body) // 3 + min(len(body) // 2,
                                                         3 * MiB)
            r = s3.get_object(BUCKET, key, byte_range=(a, b))
            check(r.body == body[a:b + 1],
                  f"degraded ranged GET {key} [{a}-{b}] differs")
        ops1 = tpu_ops(endpoint)
        rebuilt = sum(v - ops0.get(k, 0.0) for k, v in ops1.items()
                      if k[1] in ("decode", "matmul")
                      and k[0] == args.backend)
        big = sum(1 for k in victims if not k.startswith(("packed",
                                                           "inline")))
        check(rebuilt >= big,
              f"mt_tpu_ops_total{{op=decode|matmul,backend="
              f"{args.backend}}} rose by {rebuilt} over {big} degraded "
              f"objects: the reads were not rebuilt by the device codec")
        report["degraded"] = {"objects": len(victims),
                              "shards_removed": len(lost),
                              "device_decode_ops": rebuilt}
        report["phases"]["degraded"] = round(time.monotonic() - t, 2)
        log(f"degraded: {len(victims)} objects correct, "
            f"{rebuilt:.0f} device decode/matmul ops")

        # 5. heal ----------------------------------------------------
        t = time.monotonic()
        for key, idxs in victims.items():
            res = adm.heal(BUCKET, prefix=key)
            objs = [o for o in res["objects"] if o["object"] == key]
            check(len(objs) == 1 and objs[0].get("after_ok") == DRIVES,
                  f"heal {key}: {objs}")
            sh = read_shards(dirs, key)
            for i in idxs:
                check(i in sh and sh[i]["parts"] == lost[(key, i)],
                      f"heal {key}: shard {i} not byte-equal to the one "
                      f"removed")
            r = s3.get_object(BUCKET, key)
            check(r.body == bodies[key], f"GET after heal {key}")
        report["phases"]["heal"] = round(time.monotonic() - t, 2)
        log(f"heal: {len(lost)} shards rebuilt byte-equal")

        # 6. read the server back ------------------------------------
        deadline = time.monotonic() + 120
        info = codec_info(adm)
        while info["md5"]["configured"] == "auto" \
                and info["md5"]["auto_choice"] is None \
                and time.monotonic() < deadline:
            time.sleep(1.0)                # the probe runs off-path
            info = codec_info(adm)
        dev = info["device"]
        report["device"] = dev
        report["backends"] = info["backends"]
        report["md5"] = info["md5"]
        report["native"] = info["native"]
        report["per_size_class"] = per_size
        report["server_clock"] = where_the_time_went(endpoint)
        report["compile_cache_entries"] = {
            "before": info0["device"]["compile_cache"]["entries"],
            "after": dev["compile_cache"]["entries"]}
        for name, st in info["native"].items():
            check(st["loaded"], f"server: native {name} did not load: "
                  f"{st['error']}")
        check("libmt_hash.so" in info["native"],
              f"server never loaded libmt_hash.so: {sorted(info['native'])}")
        if len(endpoints) > 1:
            report["node_devices"] = []
            for ep in endpoints:
                d = codec_info(AdminClient(ep, KEY, SECRET))["device"]
                report["node_devices"].append(d)
                check(d["compile"]["compiles"] > 0,
                      f"node at {ep} compiled nothing: its chip sat idle")
                if not args.tiny:
                    check((d["platform"], d["device_count"]) == ("tpu", 1),
                          f"node at {ep} sees {d['device_count']} "
                          f"{d['platform']} device(s), not its one chip")
        if args.backend == "mesh" and dev["device_count"] > 1:
            idle = [d["id"] for d in dev["devices"]
                    if not d["peak_bytes_in_use"]]
            check(not idle, f"devices {idle} allocated nothing: the "
                  f"mesh did not reach every chip")
        check("jax" not in sys.modules, "the parent imported jax")
    finally:
        stop_servers(procs, endpoints)
        report["server_log_tail"] = {}
        for path in logs:
            try:
                with open(path, "rb") as f:
                    tail = f.read()[-3000:].decode(errors="replace")
            except OSError:
                tail = ""
            report["server_log_tail"][os.path.basename(path)] = tail
        _write_report(args, report)
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)


def _write_report(args, report: dict) -> None:
    """Full report where the chip tool brings it back from."""
    try:
        out = os.path.join(HERE, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        name = "chip_smoke_" + args.backend \
            + (f"_nodes{args.nodes}" if args.nodes > 1 else "") \
            + ("_tiny" if args.tiny else "") + args.tag + ".json"
        with open(os.path.join(out, name), "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    except OSError as e:
        log(f"report not written: {e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="tpu", choices=["tpu", "mesh"])
    ap.add_argument("--nodes", type=int, default=1,
                    help="N>1: N one-chip `node` processes instead of "
                         "one `server` (pinned by TPU_VISIBLE_CHIPS)")
    ap.add_argument("--tiny", action="store_true",
                    help="same phases, small sizes; the only mode that "
                         "accepts a platform other than tpu")
    ap.add_argument("--workdir", default=None,
                    help="parent of the drive directories (default: the "
                         "system temp dir)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the drive directories")
    ap.add_argument("--tag", default="",
                    help="suffix of the report file under chiprun_out/")
    args = ap.parse_args()
    report: dict = {}
    try:
        run(args, report)
    except Exception as e:  # noqa: BLE001 — any phase error is a failure
        for name, tail in report.get("server_log_tail", {}).items():
            log(f"--- {name} (tail) ---\n{tail}")
        log(f"FAILED: {e}" if isinstance(e, Failed)
            else f"FAILED: {type(e).__name__}: {e}")
        return 1
    dev = report["device"]
    print(json.dumps({k: v for k, v in report.items()
                      if k != "server_log_tail"}, sort_keys=True))
    result = {"ok": True, "device": {"platform": dev["platform"],
                                      "kind": dev["device_kind"],
                                      "count": dev["device_count"]}}
    if args.tiny:
        result["tiny"] = True
    print(json.dumps(result), flush=True)
    return 0


T0 = time.monotonic()

if __name__ == "__main__":
    sys.exit(main())
