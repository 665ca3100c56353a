"""Multi-node cluster assembly — the distributed deployment path
(cmd/server-main.go:389 serverMain + cmd/endpoint*.go topology, rebuilt
for host-RPC + device-compute).

Each node runs: an RPC server exporting its local drives (storage service)
and lock table (lock service), plus the S3 frontend over an object layer
whose drive list mixes local XLStorage and RemoteStorage clients in the
SAME global order on every node — so quorum, distribution, and healing
agree cluster-wide.  Namespace locks are dsync DRWMutexes over every
node's locker.
"""

from __future__ import annotations

from dataclasses import dataclass

from .objectlayer.sets import ErasureSets
from .parallel.dsync import (LocalLocker, NamespaceLock, RemoteLocker,
                             register_lock_service)
from .parallel.rpc import RPCClient, RPCServer
from .storage.format import load_or_init_format
from .storage.remote import RemoteStorage, register_storage_service
from .storage.xl_storage import XLStorage


@dataclass
class NodeSpec:
    """One host in the cluster layout: (endpoint filled at runtime)."""
    node_id: str
    drive_dirs: list[str]
    endpoint: str = ""


class Node:
    """A running cluster member: RPC services + its view of the object
    layer (every node can serve any request, cmd/routers.go:30-38)."""

    def __init__(self, spec: NodeSpec, all_specs: list[NodeSpec],
                 secret: str, set_drive_count: int | None = None,
                 host: str = "127.0.0.1", port: int = 0, tls=None,
                 **set_kwargs):
        self.spec = spec
        self.secret = secret
        self.tls = tls
        if tls is not None:
            # outbound internode clients (this node's RemoteStorage /
            # RemoteLocker links) resolve their CA-pinned context +
            # client identity through the process-global registry
            from .secure import transport as _tls_transport
            _tls_transport.configure(tls)
        self.drives = {f"drive{i}": XLStorage(d)
                       for i, d in enumerate(spec.drive_dirs)}
        self.locker = LocalLocker()
        self.rpc = RPCServer(secret, host=host, port=port, tls=tls)
        register_storage_service(self.rpc, self.drives)
        register_lock_service(self.rpc, self.locker)
        self.rpc.start()
        spec.endpoint = self.rpc.endpoint
        self._all_specs = all_specs
        self._set_kwargs = set_kwargs
        self._set_drive_count = set_drive_count
        self.layer: ErasureSets | None = None

    def assemble(self) -> ErasureSets:
        """Build this node's object layer once every peer endpoint is
        known (bootstrap rendezvous, cmd/bootstrap-peer-server.go:162)."""
        disks = []
        lockers = []
        for spec in self._all_specs:
            local = spec.node_id == self.spec.node_id
            if local:
                lockers.append(self.locker)
            else:
                client = RPCClient(spec.endpoint, self.secret)
                lockers.append(RemoteLocker(client))
            for i in range(len(spec.drive_dirs)):
                if local:
                    disks.append(self.drives[f"drive{i}"])
                else:
                    disks.append(RemoteStorage(
                        RPCClient(spec.endpoint, self.secret), f"drive{i}"))
        n = len(disks)
        sdc = self._set_drive_count or n
        assert n % sdc == 0
        fmt = load_or_init_format(disks, n // sdc, sdc)
        # drive lifecycle wrappers + reconnect monitor: offline drives
        # fail fast, returned drives are identity-verified, wiped drives
        # are reformatted and the owning set healed
        # (cmd/erasure-sets.go:196-332)
        from .storage import health as health_mod
        disks, bind = health_mod.wrap_with_heal(disks, fmt, sdc)
        self.layer = ErasureSets(
            disks, n // sdc, sdc, deployment_id=fmt.id,
            distribution_algo=fmt.distribution_algo,
            ns_lock=NamespaceLock(lockers), **self._set_kwargs)
        bind(self.layer)
        self.monitor = self.layer.start_drive_monitor()
        return self.layer

    def stop(self) -> None:
        if getattr(self, "monitor", None) is not None:
            self.monitor.stop()
        self.rpc.stop()


def start_cluster(specs: list[NodeSpec], secret: str,
                  set_drive_count: int | None = None, tls=None,
                  **set_kwargs) -> list[Node]:
    """Boot all nodes, then assemble each node's layer (first node formats,
    the rest adopt — waitForFormatErasure analog).  ``tls`` (a
    secure.certs.CertManager) encrypts the whole internode plane:
    every RPC listener serves the internode identity and requires
    CA-signed client certificates, every internode client presents
    one."""
    nodes = [Node(s, specs, secret, set_drive_count, tls=tls,
                  **set_kwargs)
             for s in specs]
    for node in nodes:
        node.assemble()
    return nodes


def wait_for_peers(specs: list[NodeSpec], secret: str, self_id: str,
                   timeout: float = 60.0) -> None:
    """Poll every peer's RPC ping until the whole topology answers
    (verifyServerSystemConfig / bootstrap rendezvous,
    cmd/bootstrap-peer-server.go:162) — multi-process nodes start in any
    order and must not assemble before their peers listen."""
    import time

    from .parallel.rpc import RPCError

    deadline = time.monotonic() + timeout
    pending = [s for s in specs if s.node_id != self_id]
    while pending:
        still = []
        for spec in pending:
            try:
                c = RPCClient(spec.endpoint, secret, timeout=2.0)
                if c.call("sys", "ping") != "pong":
                    still.append(spec)
            except RPCError as e:
                if e.error_type == "AuthError":
                    # a secret mismatch never resolves by waiting —
                    # surface the misconfiguration immediately
                    raise
                still.append(spec)
            except Exception:  # noqa: BLE001 — not up yet
                still.append(spec)
        pending = still
        if pending:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "peers never came up: "
                    + ", ".join(s.node_id for s in pending))
            time.sleep(0.25)


def _wait_for_leader_format(leader: NodeSpec, secret: str,
                            timeout: float = 60.0) -> None:
    """Poll the leader's first drive until format.json exists."""
    import time

    from .storage.format import FORMAT_FILE
    from .storage.xl_storage import SYS_DIR

    client = RPCClient(leader.endpoint, secret)
    remote = RemoteStorage(client, "drive0")
    deadline = time.monotonic() + timeout
    while True:
        try:
            remote.read_all(SYS_DIR, FORMAT_FILE)
            return
        except Exception:  # noqa: BLE001 — leader hasn't formatted yet
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"leader {leader.node_id} never wrote format.json")
            time.sleep(0.25)


def run_node(self_id: str, specs: list[NodeSpec], secret: str,
             s3_address: str = "127.0.0.1:0",
             set_drive_count: int | None = None,
             access_key: str = "minioadmin",
             secret_key: str = "minioadmin", tls=None, **set_kwargs):
    """One real cluster member process: RPC services on the DECLARED
    endpoint (so peers can dial before rendezvous), wait for the
    topology, assemble, serve S3.  Returns (node, s3_server).

    ``tls`` may be a CertManager; when omitted, the ``tls`` kvconfig
    subsystem (env: MT_TLS_ENABLE / MT_TLS_CERTS_DIR) is consulted —
    a declared ``https://`` topology then comes up fully encrypted on
    both planes."""
    from .s3.server import S3Server

    if tls is None:
        from .secure.certs import CertManager
        from .utils.kvconfig import Config
        tls = CertManager.from_config(Config())
    spec = next(s for s in specs if s.node_id == self_id)
    if not spec.endpoint:
        raise ValueError(f"node {self_id} needs a declared endpoint")
    u = spec.endpoint.removeprefix("https://").removeprefix("http://")
    rhost, _, rport = u.rpartition(":")
    node = Node(spec, specs, secret, set_drive_count,
                host=rhost or "127.0.0.1", port=int(rport), tls=tls,
                **set_kwargs)
    # Node re-derives spec.endpoint from the bound socket; with a fixed
    # port they agree with what peers dialed
    wait_for_peers(specs, secret, self_id)
    # first-boot formatting is leader-only (waitForFormatErasure: "first
    # node creates format, others wait") — concurrent init on multiple
    # nodes would mint divergent deployment ids
    if specs[0].node_id != self_id:
        _wait_for_leader_format(specs[0], secret)
    layer = node.assemble()
    shost, _, sport = s3_address.rpartition(":")
    srv = S3Server(layer, access_key=access_key, secret_key=secret_key,
                   host=shost or "127.0.0.1", port=int(sport), tls=tls)
    srv.node_name = self_id     # traces/logs name the serving node
    srv.api_stats.label = self_id
    from .obs import trace as _obs_trace
    _obs_trace.set_node_name(self_id)   # subsystem spans too
    srv.iam.load()
    # peer control-plane service: IAM/bucket-metadata changes propagate
    # to every node immediately; trace/log streams aggregate cluster-wide
    # (cmd/peer-rest-common.go:27-61)
    from .parallel.peer import PeerNotifier, register_peer_service
    register_peer_service(node.rpc, srv)
    srv.attach_peers(PeerNotifier(
        [RPCClient(s.endpoint, secret) for s in specs
         if s.node_id != self_id]))
    # every node tracks updates (peer mark_change lands here); the
    # LEADER runs the global crawler + heal sweep — this build's walks
    # cover the whole layer, so per-node copies would duplicate scans
    # (the reference crawls per-local-drive instead,
    # cmd/server-main.go:499)
    from .background.tracker import DataUpdateTracker
    srv.attach_tracker(DataUpdateTracker())
    if specs[0].node_id == self_id:
        import os as _os

        from .background.crawler import Crawler
        from .background.heal import BackgroundHealer
        from .objectlayer.tiering import transition_fn
        srv.crawler = Crawler(
            layer, bucket_meta=srv.bucket_meta,
            interval_s=float(_os.environ.get("MT_CRAWL_INTERVAL_S",
                                             "60")),
            transition_fn=transition_fn(srv.transition),
            tracker=srv.tracker)
        srv.healer = BackgroundHealer(
            layer,
            interval_s=float(_os.environ.get("MT_HEAL_INTERVAL_S",
                                             "3600")),
            deep_every=int(_os.environ.get("MT_HEAL_DEEP_EVERY", "8")))
        srv.attach_background(srv.crawler, srv.healer)
    srv.start()
    return node, srv
