"""Peer control-plane RPC service (cmd/peer-rest-{client,server,common}.go).

Cross-node coherence for the control plane: when one node mutates IAM or
a bucket's metadata, it fans the change notification to every peer so
their in-memory caches reload IMMEDIATELY instead of serving stale
policy until a cache happens to expire (peerRESTMethodLoadBucketMetadata
/ LoadUser / LoadPolicy, cmd/peer-rest-common.go:27-61).  The service
also exposes trace/log tails so one admin endpoint can aggregate
observability streams across the cluster (peerRESTMethodTrace :54,
peerRESTMethodLog :56).
"""

from __future__ import annotations

import threading
import time

from .rpc import RPCClient, RPCServer


def register_peer_service(rpc: RPCServer, srv) -> None:
    """Export a node's control-plane reload + observability hooks
    (peer-rest-server.go handler table).  ``srv`` is the node's
    S3Server."""

    def _evict_bucket_seen(layer, bucket: str) -> None:
        """Drop a bucket from every nested layer's existence cache so a
        peer's delete_bucket is visible here immediately rather than
        after the 3 s TTL."""
        from ..objectlayer.metacache import leaf_layers_of
        for leaf in leaf_layers_of(layer):
            getattr(leaf, "_buckets_seen", {}).pop(bucket, None)

    def reload_bucket_meta(bucket: str) -> bool:
        srv.bucket_meta.invalidate(bucket)
        _evict_bucket_seen(srv.layer, bucket)
        return True

    def reload_iam() -> bool:
        srv.iam.load()
        return True

    def trace_since(seq: int, limit: int = 500, types=None):
        """Trace-ring poll; ``types`` is the aggregator's wanted trace
        types — subsystem-span capture is only leased when a deep type
        is wanted, items are filtered server-side so http-only
        aggregation never ships deep spans over the wire.  An ABSENT
        ``types`` is a pre-deep-tracing caller (rolling upgrade): it
        gets exactly the old behavior — http records only, no deep
        lease.  The explicit sentinel ``["all"]`` streams everything."""
        from ..obs import trace as _trace
        want = set(types) if types is not None else {"http"}
        if "all" in want:
            _trace.lease_deep_ring()
            want = None
        elif want - {"http"}:
            _trace.lease_deep_ring()
        latest, items = srv.trace_hub.since(seq, limit)
        if want is not None:
            items = [i for i in items
                     if i.get("type", "http") in want]
        return {"seq": latest, "items": items}

    def log_recent(n: int = 100):
        return srv.logger.recent(n)

    def mark_change(bucket: str, object_name: str = "") -> bool:
        """A peer's write happened: mark this node's update tracker so
        cached listings for the bucket go stale immediately instead of
        after the metacache TTL (cmd/data-update-tracker.go fan-in +
        cmd/metacache-bucket.go consult).  The hot-read plane rides
        the same fan-out: an overwrite/delete on ANY node evicts this
        node's cached windows and fences its in-flight fills — a hit
        was never stale anyway (every hit revalidates against a quorum
        metadata read), the eviction frees the bytes promptly."""
        if srv.tracker is not None:
            srv.tracker.mark(bucket, object_name)
        else:
            from ..objectlayer.metacache import managers_of
            for mc in managers_of(srv.layer):
                mc.invalidate(bucket)  # no tracker: hard-drop instead
        from ..objectlayer.metacache import leaf_layers_of
        for leaf in leaf_layers_of(srv.layer):
            plane = getattr(leaf, "hotread", None)
            if plane is not None:
                if object_name:
                    plane.invalidate(bucket, object_name)
                else:
                    plane.invalidate_bucket(bucket)
        if not object_name:
            # bucket-level change (create/delete): existence cache too
            _evict_bucket_seen(srv.layer, bucket)
        return True

    # inter-node throughput probes (peerRESTMethodNetInfo role,
    # cmd/peer-rest-common.go:29-36): the caller times pushing bytes
    # up and pulling bytes back over the REAL authed RPC transport
    def netperf_upload(data: bytes = b"") -> int:
        return len(data)

    def netperf_download(n: int = 0) -> bytes:
        return b"\xa5" * min(int(n), 8 << 20)

    # -- cluster self-measurement (peerRESTMethodSpeedtest /
    # peerRESTMethodDriveSpeedtest / peerRESTMethodMetrics /
    # peerRESTMethodStartProfiling + cmd/utils.go getProfileData) -----

    def metrics_render() -> dict:
        """This node's full exposition document, server-labelled, plus
        the node name so the aggregator's health marks
        (mt_node_scrape_ok) join against the document's ``server``
        label instead of the RPC endpoint."""
        from ..admin.handlers import _render_local
        return {"node": srv.node_name,
                "doc": _render_local(srv, node=srv.node_name)}

    def profile_start(kinds: str = "cpu"):
        from ..obs import profiling
        return profiling.start(kinds)

    def profile_stop():
        """{filename: dump bytes} — the aggregator renames per node."""
        from ..obs import profiling
        return profiling.stop_dumps()

    def speedtest_object(size: int = 1 << 20, duration_s: float = 1.0,
                         concurrency: int = 0):
        from ..obs import selftest
        out = selftest.object_speedtest(srv.layer, size=size,
                                        duration_s=duration_s,
                                        concurrency=concurrency)
        out["node"] = srv.node_name
        return out

    def speedtest_drive(file_size: int = 4 << 20):
        from ..obs import selftest
        return {"node": srv.node_name,
                "drives": selftest.drive_speedtest(
                    selftest.local_drive_paths(srv.layer),
                    file_size=file_size)}

    def speedtest_tpu(size: int = 4 << 20, k: int = 4, m: int = 2,
                      block_size: int = 1 << 20):
        from ..obs import selftest
        out = selftest.tpu_codec_speedtest(size=size, k=k, m=m,
                                           block_size=block_size)
        out["node"] = srv.node_name
        return out

    def background_status():
        from ..admin.handlers import background_status as _bg
        out = _bg(srv)
        out["node"] = srv.node_name
        return out

    # telemetry-egress plane (admin `targets` / `targets/replay`
    # aggregation): this node's delivery-target state machine rows, and
    # the synchronous store replay kick (obs/egress.py)
    def target_status():
        return {"node": srv.node_name, "targets": srv.egress.status()}

    def target_replay():
        return {"node": srv.node_name,
                "replayed": srv.egress.replay_all()}

    # request X-ray + forensic planes (admin `xray` / `forensics` /
    # `healthinfo?scope=cluster` aggregation — the OBD fan-out shape,
    # cmd/healthinfo.go + peer drill-downs)
    def xray_query(api: str = "", min_duration_ms: float = 0.0,
                   errors_only: bool = False, limit: int = 100,
                   snapshot: bool = False):
        from ..admin.handlers import xray_reply
        return xray_reply(srv, api=api,
                          min_duration_ms=min_duration_ms,
                          errors_only=errors_only, limit=limit,
                          snapshot=snapshot)

    def healthinfo_collect(perf: bool = False):
        from ..admin.handlers import _drive_paths, _node_system_info
        from ..obs import healthinfo as _hi
        doc = _hi.collect(_drive_paths(srv), perf=perf)
        doc["node"] = srv.node_name
        doc["system"] = _node_system_info(srv)
        return doc

    def forensic_list():
        from ..admin.handlers import forensic_inventory
        return forensic_inventory(srv)

    def trace_tree_query(rid: str = "", api: str = "",
                         min_duration_ms: float = 0.0,
                         errors_only: bool = False, limit: int = 20,
                         rids=()):
        from ..obs import tracetree as _tt
        return _tt.tree_reply(srv, rid=rid, api=api,
                              min_duration_ms=min_duration_ms,
                              errors_only=errors_only, limit=limit,
                              rids=tuple(rids or ()))

    # SLO watchdog plane (admin `metrics-history` / `alerts`
    # aggregation): same shared builders as the local routes, so the
    # local leg and the peer leg can never drift apart in shape
    def history_query(family: str = "", window_s: float = 1800.0,
                      step_s: float = 60.0, agg: str = "last"):
        from ..admin.handlers import history_doc
        return {"node": srv.node_name,
                "doc": history_doc(srv, family=family,
                                   window_s=window_s, step_s=step_s,
                                   agg=agg, node=srv.node_name)}

    def alerts_query():
        from ..admin.handlers import alerts_reply
        return alerts_reply(srv)

    # Workload attribution plane (admin `top` v2 aggregation): the
    # same shared builder as the local route, so local and peer legs
    # can never drift apart in shape
    def metering_top():
        from ..admin.handlers import metering_top_reply
        return metering_top_reply(srv)

    rpc.register("peer", {
        "reload_bucket_meta": reload_bucket_meta,
        "reload_iam": reload_iam,
        "trace_since": trace_since,
        "log_recent": log_recent,
        "mark_change": mark_change,
        "netperf_upload": netperf_upload,
        "netperf_download": netperf_download,
        "metrics_render": metrics_render,
        "profile_start": profile_start,
        "profile_stop": profile_stop,
        "speedtest_object": speedtest_object,
        "speedtest_drive": speedtest_drive,
        "speedtest_tpu": speedtest_tpu,
        "background_status": background_status,
        "target_status": target_status,
        "target_replay": target_replay,
        "xray_query": xray_query,
        "healthinfo_collect": healthinfo_collect,
        "forensic_list": forensic_list,
        "trace_tree_query": trace_tree_query,
        "history_query": history_query,
        "alerts_query": alerts_query,
        "metering_top": metering_top,
    })


def measure_netperf(client: RPCClient,
                    probe_bytes: int = 4 << 20) -> dict:
    """Measured inter-node throughput to one peer over the real authed
    RPC transport (madmin NetPerf analog).  Returns MB/s both ways."""
    import time as _time
    blob = b"\x5a" * probe_bytes
    t0 = _time.perf_counter()
    n = client.call("peer", "netperf_upload", _idempotent=True,
                    data=blob)
    up_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    got = client.call("peer", "netperf_download", _idempotent=True,
                      n=probe_bytes)
    down_s = _time.perf_counter() - t0
    return {
        "endpoint": client.endpoint,
        "tx_MBps": round(n / up_s / 1e6, 1) if up_s > 0 else None,
        "rx_MBps": round(len(got) / down_s / 1e6, 1)
        if down_s > 0 else None,
        "probe_bytes": probe_bytes,
        "duration_ms": round((up_s + down_s) * 1e3, 2),
    }


class PeerNotifier:
    """Client side: fan-out of control-plane change notifications to
    every other node (NotificationSys peer calls, cmd/notification.go).
    Object and IAM changes are best-effort and asynchronous; a bucket-
    metadata change is delivered before ``bucket_meta_changed``
    returns."""

    def __init__(self, clients: list[RPCClient]):
        self.clients = clients
        # one long-lived worker + bounded queue per peer: control-plane
        # churn against a dead peer must not pile up threads; dropped
        # notifications are safe (reloads are idempotent full reloads)
        self._queues: dict = {}
        self._mu = threading.Lock()

    def _queue_for(self, c: RPCClient):
        import queue as _q
        with self._mu:
            q = self._queues.get(c.endpoint)
            if q is None:
                q = _q.Queue(maxsize=64)
                self._queues[c.endpoint] = q

                def worker():
                    while True:
                        item = q.get()
                        if item is None:        # close() sentinel
                            return
                        method, kwargs = item
                        try:
                            c.call("peer", method, _idempotent=True,
                                   **kwargs)
                        except Exception:  # noqa: BLE001 — peer down:
                            pass           # it reloads fully on restart

                threading.Thread(target=worker, daemon=True,
                                 name="mt-peer-fanout").start()
            return q

    def _fanout(self, method: str, **kwargs) -> None:
        import queue as _q
        for c in self.clients:
            try:
                self._queue_for(c).put_nowait((method, kwargs))
            except _q.Full:
                pass    # backlogged peer: a later reload covers it

    def close(self) -> None:
        """Stop the notify workers (sentinel per queue)."""
        with self._mu:
            queues = list(self._queues.values())
        for q in queues:
            try:
                q.put_nowait(None)
            except Exception:  # noqa: BLE001 — full queue: worker will
                pass           # drain and exit on the next sentinel

    def bucket_meta_changed(self, bucket: str) -> None:
        """Deliver ``reload_bucket_meta`` to every reachable peer BEFORE
        returning: a policy / object-lock / versioning document one node
        acknowledged is in force on its peers when the client has its
        reply (upstream's LoadBucketMetadata peer call waits the same
        way).  One direct call per peer, in parallel, each under the RPC
        client's own deadline, retries and breaker — not the lossy
        queue.  A peer that cannot be reached is skipped: it holds "no
        document" for at most the existence TTL and re-reads the drives
        on restart."""
        from ..obs import trace as _trace
        rid = _trace.get_request_id()
        parent = _trace.get_span_parent()

        def one(c: RPCClient):
            _trace.set_request_id(rid)
            _trace.set_span_parent(parent)
            try:
                c.call("peer", "reload_bucket_meta", _idempotent=True,
                       bucket=bucket)
            except Exception:  # noqa: BLE001 — peer down: see above
                pass

        threads = [threading.Thread(target=one, args=(c,), daemon=True,
                                    name="mt-peer-reload")
                   for c in self.clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def object_changed(self, bucket: str, object_name: str = "") -> None:
        """Async per-write fan-out feeding every peer's update tracker
        (keeps their listing caches honest without a TTL wait)."""
        self._fanout("mark_change", bucket=bucket,
                     object_name=object_name)

    def iam_changed(self) -> None:
        self._fanout("reload_iam")

    # -- observability aggregation ----------------------------------------

    def trace_tails(self, cursors: dict[str, int],
                    limit: int = 500, types=None) -> list:
        """Poll every peer's trace ring once; ``cursors`` maps endpoint →
        last-seen seq and is updated in place.  A peer first seen (or
        seen again after being unreachable at prime time) is primed at
        its CURRENT seq — a live stream never replays its history.
        ``types`` (a list of trace types, None = all) is forwarded so
        peers only capture/ship what the aggregating stream wants; the
        wire encodes "all" explicitly because an ABSENT types means a
        legacy (http-only) caller on the peer side."""
        wire_types = list(types) if types is not None else ["all"]
        merged: list = []
        for c in self.clients:
            try:
                if c.endpoint not in cursors:
                    out = c.call("peer", "trace_since", seq=0, limit=0,
                                 types=wire_types)
                    cursors[c.endpoint] = out["seq"]
                    continue
                out = c.call("peer", "trace_since",
                             seq=cursors[c.endpoint], limit=limit,
                             types=wire_types)
                if out["seq"] < cursors[c.endpoint] and not out["items"]:
                    # peer restarted: its seq space reset below our
                    # cursor — re-prime at its current head
                    cursors[c.endpoint] = out["seq"]
                    continue
                cursors[c.endpoint] = out["seq"]
                merged.extend(out["items"])
            except Exception:  # noqa: BLE001 — peer down: re-primed on
                pass           # its next successful poll
        return merged

    def log_recent_all(self, n: int = 100) -> list:
        out: list = []
        for c in self.clients:
            try:
                out.extend(c.call("peer", "log_recent", n=n))
            except Exception:  # noqa: BLE001 — downed peer: the
                pass           # aggregate serves who answered
        return out

    # -- parallel control-plane fan-out (self-measurement) -----------------

    def call_all_iter(self, method: str, timeout_s: float = 30.0,
                      idempotent: bool = True, **kwargs):
        """Call ``peer.<method>`` on every peer CONCURRENTLY, yielding
        ``(endpoint, result, error)`` as replies land (streaming
        speedtest lines).  One slow peer cannot serialize the others,
        and a peer that misses the deadline yields a ``timeout`` error
        instead of stalling the aggregate — its thread is left to die
        with the daemon flag (the RPC deadline bounds it).

        ``idempotent=False`` for one-shot methods (profile_stop: a
        replay after a half-dead keep-alive finds the session already
        stopped and would silently drop that node's dumps; peer
        speedtests: a replay re-runs the whole measured load)."""
        import queue as _q

        from ..obs import critpath as _critpath
        from ..obs import trace as _trace
        done: _q.Queue = _q.Queue()
        # propagate the causal identity into the fan-out threads so
        # every peer leg's RPC span parents under the caller's span
        # (and carry the span parent whenever the request id rides —
        # the span-discipline contract)
        rid = _trace.get_request_id()
        parent = _trace.get_span_parent()
        labels = [c.endpoint for c in self.clients]
        ends = [0] * len(self.clients)
        errs: list = [None] * len(self.clients)
        t0 = _critpath.now_ns()

        def one(i: int, c: RPCClient):
            _trace.set_request_id(rid)
            _trace.set_span_parent(parent)
            try:
                r = c.call("peer", method, _idempotent=idempotent,
                           _timeout=timeout_s, **kwargs)
                ends[i] = _critpath.now_ns()
                done.put((c.endpoint, r, ""))
            except Exception as e:  # noqa: BLE001 — peer down/slow
                errs[i] = e
                ends[i] = _critpath.now_ns()
                done.put((c.endpoint, None,
                          f"{type(e).__name__}: {e}"))

        def record_gating():
            # the aggregation gate is the LAST reply; k = n-1 makes
            # the trail histogram read "how far the slowest peer
            # trailed the rest" (an all-wait has no partial quorum)
            n = len(self.clients)
            if n > 1:
                _critpath.record("rpc", max(1, n - 1), labels,
                                 list(ends), t0, errs=errs)

        for i, c in enumerate(self.clients):
            threading.Thread(target=one, args=(i, c), daemon=True,
                             name="mt-peer-call").start()
        deadline = time.monotonic() + timeout_s
        pending = {c.endpoint for c in self.clients}
        while pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                record_gating()
                for ep in sorted(pending):
                    yield ep, None, "timeout"
                return
            try:
                ep, result, err = done.get(timeout=remaining)
            except _q.Empty:
                continue
            pending.discard(ep)
            yield ep, result, err
        record_gating()

    def call_all(self, method: str, timeout_s: float = 30.0,
                 idempotent: bool = True, **kwargs) -> list:
        """Blocking form of :meth:`call_all_iter`."""
        return list(self.call_all_iter(method, timeout_s=timeout_s,
                                       idempotent=idempotent, **kwargs))
