"""Server bootstrap + CLI (cmd/server-main.go:389 serverMain, L0).

``python -m minio_tpu server /data1 /data2 ...`` boots a single-node
server: drive init + format, set sizing, object layer assembly, IAM load,
S3 + admin frontend.  Distributed deployments assemble via
minio_tpu.cluster (each host lists every node's drives in the same
order, as the reference does with ellipses endpoints).
"""

from __future__ import annotations

import argparse
import os
import sys

from .objectlayer.sets import ErasureSets
from .s3.server import S3Server

# set sizing (cmd/endpoint-ellipses.go:44 setSizes{4..16})
SET_SIZES = list(range(16, 3, -1))


def choose_set_drive_count(n: int, override: int | None = None) -> int:
    """Largest valid set size dividing the drive count (getSetIndexes,
    cmd/endpoint-ellipses.go:132); small counts (1-3) form one set."""
    if override:
        if n % override != 0:
            raise ValueError(f"drive count {n} not divisible by "
                             f"set size {override}")
        return override
    if n < 4:
        return n
    for size in SET_SIZES:
        if n % size == 0:
            return size
    raise ValueError(f"no valid erasure set size for {n} drives "
                     f"(need a divisor in 4..16)")


def build_server(dirs: list[str], address: str = "127.0.0.1:9000",
                 access_key: str | None = None,
                 secret_key: str | None = None,
                 set_drive_count: int | None = None,
                 backend: str = "auto", block_size: int | None = None,
                 region: str = "us-east-1") -> S3Server:
    access_key = access_key or os.environ.get("MT_ROOT_USER", "minioadmin")
    secret_key = secret_key or os.environ.get("MT_ROOT_PASSWORD",
                                              "minioadmin")
    for d in dirs:
        os.makedirs(d, exist_ok=True)
    sdc = choose_set_drive_count(len(dirs),
                                 set_drive_count or
                                 int(os.environ.get(
                                     "MT_ERASURE_SET_DRIVE_COUNT", 0))
                                 or None)
    kwargs = {"backend": backend}
    if block_size:
        kwargs["block_size"] = block_size
    layer = ErasureSets.from_dirs(dirs, len(dirs) // sdc, sdc, **kwargs)
    layer.start_drive_monitor()
    host, _, port = address.rpartition(":")
    srv = S3Server(layer, access_key=access_key, secret_key=secret_key,
                   region=region, host=host or "0.0.0.0", port=int(port))
    srv.iam.load()
    # background services whose lifecycle follows the server's
    # (cmd/server-main.go initDataCrawler + initBackgroundHealing):
    # the data crawler feeds usage metrics/ILM, the heal sweep repairs
    # drift; both intervals are env-tunable and the crawler shares the
    # server's update tracker so listings invalidate on writes
    from .background.crawler import Crawler
    from .background.heal import BackgroundHealer
    from .background.tracker import DataUpdateTracker
    from .objectlayer.tiering import transition_fn
    tracker = DataUpdateTracker()
    srv.attach_tracker(tracker)
    crawler = Crawler(
        layer, bucket_meta=srv.bucket_meta,
        interval_s=float(os.environ.get("MT_CRAWL_INTERVAL_S", "60")),
        transition_fn=transition_fn(srv.transition), tracker=tracker)
    healer = BackgroundHealer(
        layer,
        interval_s=float(os.environ.get("MT_HEAL_INTERVAL_S", "3600")),
        deep_every=int(os.environ.get("MT_HEAL_DEEP_EVERY", "8")))
    srv.healer = healer            # mt_heal_* metrics + admin heal state
    srv.crawler = crawler
    srv.attach_background(crawler, healer)
    return srv


def build_gateway_server(kind: str, target: str,
                         address: str = "127.0.0.1:9000",
                         access_key: str | None = None,
                         secret_key: str | None = None,
                         cache_dirs: list[str] | None = None,
                         region: str = "us-east-1") -> S3Server:
    """`minio gateway <kind>` analog (cmd/gateway-main.go): the same S3
    frontend over a foreign backend, optionally fronted by the disk
    cache (cmd/disk-cache.go:88 deploys cacheObjects for gateways)."""
    from . import gateway as gw

    access_key = access_key or os.environ.get("MT_ROOT_USER", "minioadmin")
    secret_key = secret_key or os.environ.get("MT_ROOT_PASSWORD",
                                              "minioadmin")
    cls = gw.lookup(kind)
    if kind == "s3":
        g = cls(target,
                os.environ.get("MT_GATEWAY_ACCESS_KEY", access_key),
                os.environ.get("MT_GATEWAY_SECRET_KEY", secret_key),
                region)
    else:
        g = cls(target)
    layer = g.new_gateway_layer()
    if cache_dirs:
        from .objectlayer.diskcache import CacheObjects
        layer = CacheObjects(layer, cache_dirs)
    host, _, port = address.rpartition(":")
    srv = S3Server(layer, access_key=access_key, secret_key=secret_key,
                   region=region, host=host or "0.0.0.0", port=int(port))
    srv.iam.load()
    return srv


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="minio_tpu", description="TPU-native S3 object storage server")
    sub = parser.add_subparsers(dest="command", required=True)
    pg = sub.add_parser("gateway", help="serve S3 over a foreign backend")
    pg.add_argument("kind", help="nas | s3 | azure | gcs | hdfs")
    pg.add_argument("target", help="mount path (nas) or endpoint URL (s3)")
    pg.add_argument("--address", default="0.0.0.0:9000")
    pg.add_argument("--access-key", default=None)
    pg.add_argument("--secret-key", default=None)
    pg.add_argument("--cache-dir", action="append", default=None,
                    help="disk cache drive (repeatable)")
    pg.add_argument("--region", default="us-east-1")
    pn = sub.add_parser("node", help="start one distributed cluster node")
    pn.add_argument("--node-id", required=True)
    pn.add_argument("--secret", default=None,
                    help="internode RPC secret (MT_CLUSTER_SECRET)")
    pn.add_argument("--address", default="127.0.0.1:0",
                    help="S3 frontend address")
    pn.add_argument("--set-drive-count", type=int, default=None)
    pn.add_argument("--backend", default="auto",
                    choices=["auto", "tpu", "mesh", "numpy"])
    pn.add_argument("peers", nargs="+",
                    help="topology: id=host:rpcport=dir1,dir2 per node, "
                         "SAME order on every node")
    ps = sub.add_parser("server", help="start the object storage server")
    ps.add_argument("dirs", nargs="+", help="drive directories")
    ps.add_argument("--address", default="0.0.0.0:9000")
    ps.add_argument("--access-key", default=None)
    ps.add_argument("--secret-key", default=None)
    ps.add_argument("--set-drive-count", type=int, default=None)
    ps.add_argument("--backend", default="auto",
                    choices=["auto", "tpu", "mesh", "numpy"],
                    help="erasure compute backend: tpu = one chip, "
                         "mesh = every chip JAX sees, numpy = host; an "
                         "explicit tpu/mesh without a TPU exits unless "
                         "JAX_PLATFORMS=cpu opts into the CPU")
    ps.add_argument("--block-size", type=int, default=None)
    ps.add_argument("--region", default="us-east-1")
    args = parser.parse_args(argv)

    codec_line = ""
    if args.command in ("node", "server"):
        # resolve the codec backend ONCE, before anything is built, and
        # say what it resolved to and on which device: the name the
        # operator typed is not evidence of where the bytes are coded
        from .ops.codec import resolve_backend
        try:
            backend = resolve_backend(args.backend)
        except RuntimeError as e:           # ops.device.DeviceUnavailable
            print(f"minio-tpu: {e}", file=sys.stderr, flush=True)
            return 2
        codec_line = f"backend={backend} (requested {args.backend})"
        if backend != "numpy":
            from .ops import device
            d = device.describe()
            codec_line += (
                f" platform={d['platform']} device_kind={d['device_kind']!r}"
                f" devices={d['device_count']} kernels={d['kernels']}"
                f" compile_cache={d['compile_cache']['dir']}"
                f" ({d['compile_cache']['entries']} entries)")
        args.backend = backend

    if args.command == "node":
        from .cluster import NodeSpec, run_node
        secret = args.secret or os.environ.get("MT_CLUSTER_SECRET", "")
        specs = []
        for p in args.peers:
            nid, endpoint, dirs = p.split("=", 2)
            drive_dirs = [d for d in dirs.split(",") if d]
            if nid == args.node_id:
                for d in drive_dirs:
                    os.makedirs(d, exist_ok=True)
            specs.append(NodeSpec(nid, drive_dirs,
                                  endpoint=f"http://{endpoint}"))
        if not secret:
            # the RPC plane grants full shard read/write: a well-known
            # default secret is acceptable only on loopback topologies
            if any(not s.endpoint.startswith(("http://127.", "http://localhost"))
                   for s in specs):
                parser.error("distributed nodes require --secret or "
                             "MT_CLUSTER_SECRET (refusing a default "
                             "secret on non-loopback endpoints)")
            secret = "cluster-secret"
        node, srv = run_node(args.node_id, specs, secret, args.address,
                             args.set_drive_count, backend=args.backend)
        shost = args.address.rpartition(":")[0] or "127.0.0.1"
        print(f"minio-tpu node {args.node_id}: rpc={node.rpc.endpoint} "
              f"s3=http://{shost}:{srv.port} {codec_line}", flush=True)
        try:
            srv.shutdown.wait()       # admin stop or Ctrl-C ends the node
        except KeyboardInterrupt:
            srv.stop()
        node.stop()
        return 0

    if args.command == "gateway":
        srv = build_gateway_server(args.kind, args.target, args.address,
                                   args.access_key, args.secret_key,
                                   args.cache_dir, args.region)
        print(f"minio-tpu gateway [{args.kind}] -> {args.target}",
              flush=True)
        print(f"S3 endpoint: http://{args.address}", flush=True)
        try:
            srv.httpd.serve_forever()
        except KeyboardInterrupt:
            srv.stop()
        return 0

    srv = build_server(args.dirs, args.address, args.access_key,
                       args.secret_key, args.set_drive_count,
                       args.backend, args.block_size, args.region)
    n = len(args.dirs)
    sdc = srv.layer.set_drive_count
    print(f"minio-tpu server: {n} drives, "
          f"{n // sdc} set(s) x {sdc} drives, {codec_line}", flush=True)
    print(f"S3 endpoint: http://{args.address}", flush=True)
    print(f"admin:       http://{args.address}/minio-tpu/admin/v1/info",
          flush=True)
    print(f"metrics:     http://{args.address}/minio-tpu/metrics",
          flush=True)
    try:
        srv.httpd.serve_forever()
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
