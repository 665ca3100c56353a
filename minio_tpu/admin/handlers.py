"""Admin API (cmd/admin-router.go:38, cmd/admin-handlers*.go — the
operations surface: server info, config KV, heal, user/policy management,
Prometheus metrics).

Routes live under ``/minio-tpu/admin/v1/`` on the same listener as S3
(mirroring the reference's /minio/admin/v3).  All admin calls require a
SigV4-authenticated identity allowed for ``admin:*`` actions; the metrics
endpoint is Prometheus text and public by default (configurable upstream).
"""

from __future__ import annotations

import json
import time

from ..iam import policy as iampol
from ..iam.sys import IAMError, NoSuchPolicy, NoSuchUser
from . import metrics

ADMIN_PREFIX = "/minio-tpu/admin/v1"
METRICS_PATH = "/minio-tpu/metrics"

_START = time.time()


def handle(h, srv, path: str, query: dict, payload: bytes) -> bool:
    """Dispatch admin/metrics routes; returns True when handled.

    ``h`` is the HTTP handler (gives _send/_fail/command/access_key),
    ``srv`` the S3Server (gives layer/iam/config).
    """
    if path == METRICS_PATH:
        qm = {k: v[0] for k, v in query.items()}
        if qm.get("scope") == "cluster":
            # federated scrape: this node + every peer, one document
            body = _metrics_cluster(srv, qm).encode()
        else:
            body = _render_local(srv).encode()
        h._send(200, body, content_type="text/plain; version=0.0.4")
        return True
    if not path.startswith(ADMIN_PREFIX + "/"):
        return False
    # every admin route requires an admin-capable identity
    if not srv.iam.is_allowed(h.access_key, iampol.ADMIN_ALL):
        from ..s3.server import S3Error
        raise S3Error("AccessDenied")
    route = path[len(ADMIN_PREFIX) + 1:]
    q1 = {k: v[0] for k, v in query.items()}

    def send_json(doc, status=200):
        h._send(status, json.dumps(doc).encode(),
                content_type="application/json")

    try:
        if route == "info" and h.command == "GET":
            return send_json(_server_info(srv)) or True
        if route.startswith("config"):
            return _config(h, srv, route, q1, payload, send_json)
        if route.startswith("heal") and h.command == "POST":
            return _heal(h, srv, route, q1, send_json)
        if route == "add-user" and h.command == "POST":
            doc = json.loads(payload)
            srv.iam.add_user(doc["accessKey"], doc["secretKey"],
                             doc.get("policies", []))
            return send_json({"status": "ok"}) or True
        if route == "list-users" and h.command == "GET":
            return send_json({
                u.access_key: {"status": u.status, "policies": u.policies}
                for u in srv.iam.list_users()}) or True
        if route == "remove-user" and h.command == "POST":
            srv.iam.remove_user(q1["accessKey"])
            return send_json({"status": "ok"}) or True
        if route == "set-user-status" and h.command == "POST":
            status = q1.get("status")
            if status not in ("enabled", "disabled"):
                return send_json(
                    {"error": "status must be enabled|disabled"}, 400) \
                    or True
            srv.iam.set_user_status(q1["accessKey"], status == "enabled")
            return send_json({"status": "ok"}) or True
        if route == "set-user-policy" and h.command == "POST":
            target = q1["accessKey"]
            pols = [p for p in q1.get("policies", "").split(",") if p]
            try:
                srv.iam.attach_policy(target, pols)
            except Exception as e:     # noqa: BLE001 — NoSuchUser path
                from ..iam.sys import NoSuchUser
                # only an UNKNOWN access key that looks like a DN, with
                # LDAP configured, routes to the LDAP mappedPolicy
                # store (cmd/admin-handlers-users.go LDAP sys type); a
                # real user whose key contains '=' is never misrouted
                if isinstance(e, NoSuchUser) and "=" in target \
                        and getattr(srv, "ldap", None) is not None:
                    srv.iam.set_ldap_policy(target, pols)
                else:
                    raise
            return send_json({"status": "ok"}) or True
        if route == "add-service-account" and h.command == "POST":
            doc = json.loads(payload) if payload else {}
            sa = srv.iam.new_service_account(
                doc.get("parent", h.access_key),
                doc.get("accessKey"), doc.get("secretKey"))
            return send_json({"accessKey": sa.access_key,
                              "secretKey": sa.secret_key}) or True
        if route.startswith("policy"):
            return _policy(h, srv, route, payload, send_json)
        if route == "datausageinfo" and h.command == "GET":
            # cmd/admin-handlers.go DataUsageInfoHandler: serve the
            # crawler's last persisted scan
            from ..background.crawler import load_usage
            info = load_usage(srv.layer)
            if info is None:
                return send_json({"error": "no usage data yet"}, 404) \
                    or True
            return send_json(json.loads(info.to_json())) or True
        if route == "data-usage" and h.command == "GET":
            # the quota-aware sibling of datausageinfo: the persisted
            # crawler snapshot PLUS this server's live enforcement view
            # (in-flight byte deltas charged by committed writes since
            # that snapshot) — what _check_quota actually sees
            from ..background.crawler import load_usage
            info = load_usage(srv.layer)
            usage = getattr(srv, "usage", None)
            return send_json({
                "persisted": json.loads(info.to_json())
                if info is not None else None,
                "cache": usage.snapshot_doc()
                if usage is not None else None,
            }) or True
        if route == "tier" and h.command == "GET":
            # madmin ListTiers analog — credentials never leave the server
            return send_json(
                json.loads(srv.transition.to_json(redact=True))) or True
        if route == "tier" and h.command == "PUT":
            # madmin AddTier analog: {"type":"dir"|"s3", "name", ...}
            from ..objectlayer import tiering as _tr
            from ..storage.xl_storage import SYS_DIR
            doc = json.loads(payload)
            name = doc.get("name", "")
            if not name:
                return send_json({"error": "tier name required"},
                                 400) or True
            if name in srv.transition.tiers:
                # replacing a tier would strand every stub whose
                # META_KEY resolves against the old backend
                return send_json(
                    {"error": f"tier {name!r} already exists"},
                    409) or True
            try:
                if doc.get("type") == "dir":
                    srv.transition.add_tier(_tr.DirTier(name,
                                                        doc["path"]))
                elif doc.get("type") == "s3":
                    srv.transition.add_tier(_tr.S3Tier(
                        name, doc["endpoint"], doc["bucket"],
                        doc["access_key"], doc["secret_key"],
                        doc.get("prefix", ""),
                        doc.get("region", "us-east-1")))
                else:
                    return send_json({"error": "unknown tier type"},
                                     400) or True
            except KeyError as e:
                return send_json(
                    {"error": f"missing tier config field {e}"},
                    400) or True
            blob = srv.transition.to_json()
            srv.layer._fanout(
                lambda d: d.write_all(SYS_DIR, "tiers/tiers.json", blob))
            return send_json({"status": "ok"}) or True
        if route == "service" and h.command == "POST":
            # madmin ServiceAction: stop | restart (cmd/admin-handlers.go
            # ServiceHandler).  The reply goes out before the action.
            action = q1.get("action", "")
            if action not in ("stop", "restart"):
                return send_json({"error": f"unknown action {action!r}"},
                                 400) or True
            import threading

            def later():
                time.sleep(0.2)
                if action == "restart":
                    import os
                    import sys
                    # re-exec through -m: sys.argv[0] is __main__.py,
                    # which cannot be run as a plain script (relative
                    # imports need the package context)
                    os.execv(sys.executable,
                             [sys.executable, "-m", "minio_tpu",
                              *sys.argv[1:]])
                srv.stop()
                srv.shutdown.set()      # node-mode main thread waits here
            threading.Thread(target=later, daemon=True,
                             name="mt-admin-svcact").start()
            return send_json({"status": "ok", "action": action}) or True
        if route == "storageinfo" and h.command == "GET":
            # madmin StorageInfo: per-drive capacity + online state —
            # same topology traversal as the metrics scrape
            from ..storage.health import (slow_drive_knobs,
                                          slow_drives_for_layer)
            mult, mins = slow_drive_knobs(getattr(srv, "config", None))
            verdicts = slow_drives_for_layer(srv.layer, multiple=mult,
                                             min_samples=mins)
            disks = []
            for si, d in metrics._collect_disks_with_set(srv.layer):
                if d is None:
                    disks.append({"set": si, "state": "offline"})
                    continue
                try:
                    info = d.disk_info()
                    entry = {
                        "set": si, "endpoint": d.endpoint(),
                        "state": "ok", "total": info.total,
                        "used": info.used, "free": info.free}
                    v = verdicts.get(d.endpoint())
                    if v is not None:
                        # verdicts exist only for drives this node
                        # measures (local windows); a remote drive gets
                        # NO flag rather than a silently-false one
                        entry["slow"] = bool(v["slow"])
                    disks.append(entry)
                except Exception as e:  # noqa: BLE001
                    disks.append({"set": si, "endpoint": d.endpoint(),
                                  "state": "offline", "error": str(e)})
            out = {"disks": disks, "backend": "erasure-tpu"}
            ps = getattr(srv.layer, "pool_status", None)
            if ps is not None:
                pools = ps()
                _merge_pool_usage(srv, pools)
                out["pools"] = pools
            return send_json(out) or True
        if route == "top-locks" and h.command == "GET":
            # madmin TopLocks: currently-held namespace locks
            out = []
            ns = getattr(srv.layer, "ns_lock", None)
            sets = getattr(srv.layer, "sets", None)
            lockers = []
            if ns is not None:
                lockers = ns.lockers
            elif sets:
                for s in sets:
                    lk = getattr(s, "ns_lock", None)
                    if lk is not None:
                        lockers.extend(lk.lockers)
            for lk in lockers:
                if hasattr(lk, "held"):
                    out.extend(lk.held())
            return send_json({"locks": out}) or True
        if route == "list-groups" and h.command == "GET":
            return send_json(srv.iam.list_groups()) or True
        if route == "add-user-to-group" and h.command == "POST":
            srv.iam.add_user_to_group(q1["accessKey"], q1["group"])
            return send_json({"status": "ok"}) or True
        if route == "set-group-policy" and h.command == "POST":
            doc = json.loads(payload)
            srv.iam.set_group_policy(doc["group"], doc["policies"])
            return send_json({"status": "ok"}) or True
        if route == "get-bucket-quota" and h.command == "GET":
            raw = srv.bucket_meta.get_config(q1["bucket"], "quota")
            return send_json(json.loads(raw) if raw else {}) or True
        if route == "set-bucket-quota" and h.command == "POST":
            # madmin SetBucketQuota: {"quota": bytes, "quotatype": "hard"}
            from ..bucket.quota import Quota
            bucket = q1.get("bucket", "")
            try:
                srv.layer.get_bucket_info(bucket)
                Quota.parse(payload)        # reject malformed docs now,
            except Exception as e:          # not on every later PUT
                return send_json({"error": str(e)}, 400) or True
            srv.bucket_meta.set_config(bucket, "quota", payload.decode())
            return send_json({"status": "ok"}) or True
        if route == "clear-bucket-quota" and h.command == "POST":
            # madmin SetBucketQuota with an empty doc clears; this
            # build keeps clear explicit so a malformed set can never
            # silently drop enforcement
            bucket = q1.get("bucket", "")
            try:
                srv.layer.get_bucket_info(bucket)
            except Exception as e:  # noqa: BLE001 — unknown bucket
                return send_json({"error": str(e)}, 400) or True
            srv.bucket_meta.set_config(bucket, "quota", None)
            return send_json({"status": "ok"}) or True
        if route == "kms-key-status" and h.command == "GET":
            # madmin KMSKeyStatus: round-trip an encryption probe
            try:
                key, sealed = srv.kms.generate_key(
                    {"probe": "admin"})
                ok = srv.kms.unseal_key(sealed, {"probe": "admin"}) == key
                return send_json({"key_id": srv.kms.key_id,
                                  "encryption_ok": ok,
                                  "decryption_ok": ok}) or True
            except Exception as e:  # noqa: BLE001
                return send_json({"key_id": srv.kms.key_id,
                                  "error": str(e)}, 500) or True
        if route == "list-service-accounts" and h.command == "GET":
            return send_json({
                u.access_key: {"parent": u.parent_user}
                for u in srv.iam.list_service_accounts(
                    q1.get("parent"))}) or True
        if route == "delete-service-account" and h.command == "POST":
            ak = q1.get("accessKey", "")
            try:
                u = srv.iam.get_user(ak)
            except NoSuchUser:
                return send_json({"error": "no such account"},
                                 404) or True
            if not u.parent_user or u.expiration:
                # a plain user here would cascade-delete all of its
                # service accounts — refuse non-SA targets
                return send_json(
                    {"error": f"{ak!r} is not a service account"},
                    400) or True
            srv.iam.remove_user(ak)
            return send_json({"status": "ok"}) or True
        if route == "heal-status" and h.command == "GET":
            # madmin BackgroundHealStatus analog
            healer = getattr(srv, "healer", None)
            mrf = getattr(srv, "mrf", None)
            return send_json({
                "sweep": healer.stats.to_dict() if healer else None,
                "mrf": mrf.stats.to_dict() if mrf else None}) or True
        if route == "soak-status" and h.command == "GET":
            # soak-plane visibility (minio_tpu/soak): the live scenario
            # a conductor attached to this server, or null when idle
            soak = getattr(srv, "soak", None)
            return send_json(
                soak.snapshot() if soak is not None else None) or True
        if route == "replication-stats" and h.command == "GET":
            repl = srv.replication
            return send_json(
                repl.stats.to_dict() if repl else {}) or True
        if route == "pool-status" and h.command == "GET":
            ps = getattr(srv.layer, "pool_status", None)
            if ps is None:
                return send_json({"error": "not a pooled deployment"},
                                 400) or True
            pools = ps()
            _merge_pool_usage(srv, pools)
            return send_json({"pools": pools}) or True
        if route == "pool-add" and h.command == "POST":
            # elastic expansion: attach a new erasure-sets pool under
            # live traffic; the manifest write makes it durable
            layer = srv.layer
            if not hasattr(layer, "attach_pool"):
                return send_json({"error": "not a pooled deployment"},
                                 400) or True
            doc = json.loads(payload)
            try:
                idx = layer.attach_pool(
                    doc["dirs"], int(doc["setCount"]),
                    int(doc["setDriveCount"]), **doc.get("kwargs", {}))
            except ValueError as e:
                return send_json({"error": str(e)}, 400) or True
            rb = _rebalancer(srv)
            if rb is not None:
                rb.kick()      # let the balancer spread toward it now
            return send_json({"status": "ok", "pool": idx}) or True
        if route == "pool-decommission" and h.command == "POST":
            layer = srv.layer
            if not hasattr(layer, "start_decommission"):
                return send_json({"error": "not a pooled deployment"},
                                 400) or True
            try:
                idx = layer.start_decommission(_pool_arg(q1))
            except ValueError as e:
                return send_json({"error": str(e)}, 400) or True
            rb = _rebalancer(srv)
            if rb is not None:
                rb.kick()      # start draining without waiting a cycle
            return send_json({"status": "draining", "pool": idx}) or True
        if route == "pool-decommission-abort" and h.command == "POST":
            layer = srv.layer
            if not hasattr(layer, "abort_decommission"):
                return send_json({"error": "not a pooled deployment"},
                                 400) or True
            try:
                idx = layer.abort_decommission(_pool_arg(q1))
            except ValueError as e:
                return send_json({"error": str(e)}, 400) or True
            return send_json({"status": "active", "pool": idx}) or True
        if route == "rebalance-status" and h.command == "GET":
            rb = _rebalancer(srv)
            return send_json(
                rb.status() if rb is not None else None) or True
        if route == "remove-remote-target" and h.command == "POST":
            repl = srv.replication
            if repl is None:
                return send_json({"error": "replication not enabled"},
                                 400) or True
            bucket = q1["bucket"]
            if repl.get_target(bucket) is None:
                return send_json(
                    {"error": f"no remote target for {bucket!r}"},
                    404) or True
            repl.remove_target(bucket)
            return send_json({"status": "ok"}) or True
        if route == "set-remote-target" and h.command == "POST":
            from ..background.replication import (ReplicationSys,
                                                  ReplicationTarget)
            if srv.replication is None:
                srv.replication = ReplicationSys(srv.layer, srv.bucket_meta)
                srv.replication.start()
            doc = json.loads(payload)
            bucket = doc.pop("sourceBucket")
            srv.replication.set_target(bucket, ReplicationTarget(**doc))
            return send_json({"status": "ok"}) or True
        if route == "list-remote-targets" and h.command == "GET":
            repl = srv.replication
            return send_json(
                {b: t.to_dict() for b, t in repl._targets.items()}
                if repl else {}) or True
        if route == "bandwidth" and h.command == "GET":
            repl = srv.replication
            return send_json(
                repl.monitor.report() if repl else {}) or True
        if route == "set-bandwidth-limit" and h.command == "POST":
            repl = srv.replication
            if repl is None:
                return send_json({"error": "replication not enabled"},
                                 400) or True
            repl.monitor.set_limit(q1["bucket"],
                                   int(q1.get("limit", "0")))
            return send_json({"status": "ok"}) or True
        if route == "trace" and h.command == "GET":
            # per-type filtering (`mc admin trace -a` analog): default
            # http-only so existing consumers see no new record shapes
            # OR new costs — an http-only stream registers an opt-out
            # so subsystem spans are never built for it, locally
            # (obs/trace.py http_only_consumer) or on peers (the wanted
            # types ride the trace_since poll).  ?type=storage,
            # internode,tpu (or type=all) opts into the deep spans.
            import contextlib as _ctxlib

            from ..obs import trace as _obs_trace
            flt, want = _trace_type_filter(q1)
            unknown = (want or set()) - set(_obs_trace.TRACE_TYPES)
            if unknown:
                # a typo'd type would stream nothing forever with a
                # 200 — indistinguishable from a healthy idle system
                return send_json(
                    {"error": f"unknown trace type(s) "
                              f"{sorted(unknown)}; valid: "
                              f"{list(_obs_trace.TRACE_TYPES)} or all"},
                    400) or True
            ctx = _obs_trace.http_only_consumer() \
                if want == {"http"} else _ctxlib.nullcontext()
            with ctx:
                if srv.peers is not None and q1.get("local") != "true":
                    return _stream_with_peer_traces(h, srv, q1, flt,
                                                    want)
                return _stream(h, srv.trace_hub, q1, flt)
        if route == "targets" and h.command == "GET":
            # delivery-target status across the cluster (`mc admin
            # info` target-status analog): state machine, backlog,
            # last error/success per target, peer-aggregated like
            # background-status
            out = {"node": srv.node_name,
                   "targets": srv.egress.status()}
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"node": ep, "error": err} if err else r
                    for ep, r, err in srv.peers.call_all(
                        "target_status", timeout_s=5.0)]
            return send_json(out) or True
        if route == "targets/replay" and h.command == "POST":
            # kick a synchronous replay of every store-backed target,
            # here and (unless ?local=true) on every peer.  Non-
            # idempotent on the wire: a replayed RPC would re-deliver
            # records the first pass already drained.
            out = {"node": srv.node_name,
                   "replayed": srv.egress.replay_all()}
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"node": ep, "error": err} if err else r
                    for ep, r, err in srv.peers.call_all(
                        "target_replay", timeout_s=30.0,
                        idempotent=False)]
            return send_json(out) or True
        if route == "top" and h.command == "GET":
            out = _top(srv)
            # top v2: the workload attribution sections (hot keys /
            # prefixes, top tenants by bytes/errors/p99), aggregated
            # across peers via the metering_top RPC (?local=true keeps
            # it node-local).  Absent entirely when metering is off on
            # this node and no peer reports — the v1 shape survives.
            m = getattr(srv, "metering", None)
            docs = [metering_top_reply(srv)] if m is not None else []
            if srv.peers is not None and q1.get("local") != "true":
                peer_errs = []
                for ep, r, err in srv.peers.call_all(
                        "metering_top", timeout_s=10.0):
                    if err:
                        peer_errs.append({"node": ep, "error": err})
                    elif r:
                        docs.append(r)
                if peer_errs:
                    out["peerErrors"] = peer_errs
            if docs:
                from ..obs.metering import merge_top_docs
                agg = merge_top_docs([d for d in docs if d])
                out["version"] = 2
                out["tenants"] = agg["tenants"]
                out["hotKeys"] = agg["hotKeys"]
                out["hotPrefixes"] = agg["hotPrefixes"]
                out["meteringNodes"] = agg["nodes"]
                if m is not None and docs and docs[0]:
                    out["sketch"] = docs[0].get("sketch")
            return send_json(out) or True
        if route == "log" and h.command == "GET":
            if q1.get("follow") == "true":
                return _stream(h, srv.logger.pubsub, q1)
            n_want = int(q1.get("n", "100"))
            entries = srv.logger.recent(n_want)
            if srv.peers is not None and q1.get("local") != "true":
                # merge cluster-wide by time and honor the n contract
                entries = sorted(
                    entries + srv.peers.log_recent_all(n_want),
                    key=lambda e: e.get("time", ""))[-n_want:]
            return send_json(entries) or True
        if route == "audit-recent" and h.command == "GET":
            # tail() arms the in-memory tail — entry construction is
            # gated on an actual consumer (obs/audit.py enabled)
            return send_json(
                srv.audit.tail(int(q1.get("n", "50")))) or True
        if route == "profile" and h.command == "POST":
            # cluster-wide by default (StartProfilingHandler fans the
            # start to every peer; ?local=true keeps it node-local)
            from ..obs import profiling
            kinds_csv = q1.get("profilerType", "cpu")
            try:
                kinds = profiling.start(kinds_csv)
            except ValueError as e:
                return send_json({"error": str(e)}, 400) or True
            out = {"started": kinds}
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"endpoint": ep, "error": err} if err
                    else {"endpoint": ep, "started": r}
                    for ep, r, err in srv.peers.call_all(
                        "profile_start", timeout_s=10.0,
                        kinds=kinds_csv)]
            return send_json(out) or True
        if route == "profile-download" and h.command == "GET":
            # one zip for the whole cluster: every node's dumps renamed
            # profile-cpu.<endpoint>.txt etc. (cmd/utils.go:286
            # getProfileData per-node file naming)
            from ..obs import profiling
            dumps = profiling.stop_dumps()
            if srv.peers is not None and q1.get("local") != "true":
                # per-node names only when the zip holds >1 node's
                # dumps; a standalone server keeps the plain names
                dumps = {_node_dump_name(n, srv.node_name): d
                         for n, d in dumps.items()}
                for ep, r, err in srv.peers.call_all(
                        "profile_stop", timeout_s=15.0,
                        idempotent=False):
                    if err or not isinstance(r, dict):
                        dumps[_node_dump_name("profile-error.txt", ep)] \
                            = (err or "malformed peer reply").encode()
                        continue
                    for n, d in r.items():
                        dumps[_node_dump_name(n, ep)] = d
            h._send(200, profiling.zip_dumps(dumps),
                    content_type="application/zip",
                    headers={"Content-Disposition":
                             "attachment; filename=profile.zip"})
            return True
        if route == "background-status" and h.command == "GET":
            out = background_status(srv)
            out["node"] = srv.node_name
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"node": ep, "error": err} if err else r
                    for ep, r, err in srv.peers.call_all(
                        "background_status", timeout_s=5.0)]
            return send_json(out) or True
        if route in ("speedtest", "speedtest-drive", "speedtest-tpu") \
                and h.command == "POST":
            return _speedtest(h, srv, route, q1)
        if route == "healthinfo" and h.command == "GET":
            from ..obs import healthinfo
            local = healthinfo.collect(
                _drive_paths(srv), perf=q1.get("perf") == "true")
            local["node"] = srv.node_name
            local["system"] = _node_system_info(srv)
            if q1.get("scope") != "cluster":
                return send_json(local) or True
            # cluster OBD document (cmd/healthinfo.go + `mc admin obd`
            # fan-out): every peer's health section folded into one
            # reply; a downed peer is MARKED (error + offline), never
            # fails the call
            nodes = [local]
            if srv.peers is not None:
                for ep, r, err in srv.peers.call_all(
                        "healthinfo_collect", timeout_s=15.0,
                        perf=q1.get("perf") == "true"):
                    nodes.append(
                        {"node": ep, "error": err, "offline": True}
                        if err or not isinstance(r, dict) else r)
            return send_json({"scope": "cluster", "version": "1",
                              "nodes": nodes}) or True
        if route == "xray" and h.command == "GET":
            # request X-ray: flight-recorder query (filter by api /
            # min-duration / errors-only), peer-aggregated like `top`.
            # ?snapshot=true adds a fresh system snapshot per node.
            params = _xray_params(q1)
            out = xray_reply(srv, **params)
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"node": ep, "error": err} if err else r
                    for ep, r, err in srv.peers.call_all(
                        "xray_query", timeout_s=10.0, **params)]
            return send_json(out) or True
        if route == "trace-tree" and h.command == "GET":
            # causal trace trees: the span ring assembled into
            # parent→children request trees, peer-merged so a
            # frontend root adopts its peer-side children.  Filters
            # mirror xray (?api/?min-duration-ms/?errors/?n) plus
            # ?rid= for one complete tree and ?format=otlp /
            # ?export=true for the OTLP egress shape.
            from ..obs import tracetree as _tt
            params = _trace_tree_params(q1)
            fmt = q1.get("format", "")
            export = q1.get("export") == "true"
            local = _tt.tree_reply(srv, **params)
            if srv.peers is not None and q1.get("local") != "true":
                rids = tuple(t["requestID"]
                             for t in local.get("trees", ()))
                peers = srv.peers.call_all(
                    "trace_tree_query", timeout_s=10.0,
                    rids=rids, **params)
                trees = _tt.merge_replies(
                    local, [r for _, r, err in peers if not err],
                    api=params["api"],
                    min_duration_ms=params["min_duration_ms"],
                    errors_only=params["errors_only"],
                    limit=params["limit"])
                out = {"node": srv.node_name, "scope": "cluster",
                       "trees": trees,
                       "peers": [{"node": ep, "error": err}
                                 for ep, _, err in peers if err]}
            else:
                out = {"node": srv.node_name, "scope": "local",
                       "trees": local["trees"]}
            out["spanCount"] = sum(
                _tt.span_count(t) for t in out["trees"])
            if export:
                out["exported"] = _tt.export_trees(srv, out["trees"])
            if fmt == "otlp":
                return send_json(_tt.to_otlp(
                    out["trees"], node=srv.node_name)) or True
            return send_json(out) or True
        if route == "forensics" and h.command == "GET":
            # resident forensic bundles on this node (and, unless
            # ?local=true, every peer): names/sizes/triggers — the
            # support-bundle inventory an operator collects after a
            # breach
            out = forensic_inventory(srv)
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"node": ep, "error": err} if err else r
                    for ep, r, err in srv.peers.call_all(
                        "forensic_list", timeout_s=10.0)]
            return send_json(out) or True
        if route == "forensics" and h.command == "POST":
            # manual bundle trigger (`mc admin obd` on demand): writes
            # synchronously so the reply can name the bundle
            fx = getattr(srv, "forensic", None)
            if fx is None:
                return send_json(
                    {"error": "forensic engine disabled"}, 400) or True
            fired = fx.fire("manual", {"by": h.access_key}, sync=True)
            return send_json({
                "fired": bool(fired),
                "cooldown_s": fx.cooldown_s if not fired else 0,
                "bundles": fx.bundles()}) or True
        if route == "metrics-history" and h.command == "GET":
            # telemetry history (obs/history.py rings) as one
            # exposition-style document — ?family=&window=&step=&agg=,
            # peer-merged with ``server`` labels exactly like
            # metrics?scope=cluster; a downed peer is marked
            # ``mt_node_history_ok 0``, never failed
            params = _history_params(q1)
            docs = [history_doc(srv, node=srv.node_name, **params)]
            status = [(srv.node_name, 1)]
            if srv.peers is not None and q1.get("local") != "true":
                for ep, r, err in srv.peers.call_all(
                        "history_query", timeout_s=10.0, **params):
                    if err or not isinstance(r, dict) \
                            or not isinstance(r.get("doc"), str):
                        status.append((ep, 0))
                    else:
                        docs.append(r["doc"])
                        status.append((r.get("node", ep), 1))
            marks = ["# TYPE mt_node_history_ok gauge"]
            for server, ok in status:
                esc = metrics._escape_label(server)
                marks.append(
                    f'mt_node_history_ok{{server="{esc}"}} {ok}')
            text = metrics.merge_expositions(docs) \
                + "\n".join(marks) + "\n"
            h._send(200, text.encode(),
                    content_type="text/plain; version=0.0.4")
            return True
        if route == "alerts" and h.command == "GET":
            # watchdog alerts (active + recent), peer-aggregated like
            # xray/forensics; ?local=true keeps it to this node
            out = alerts_reply(srv)
            if srv.peers is not None and q1.get("local") != "true":
                out["peers"] = [
                    {"node": ep, "error": err} if err else r
                    for ep, r, err in srv.peers.call_all(
                        "alerts_query", timeout_s=10.0)]
            return send_json(out) or True
        if route == "netperf" and h.command == "POST":
            # madmin NetPerf analog (peerRESTMethodNetInfo): throughput
            # to every peer over the real authed internode transport.
            # Probes run CONCURRENTLY — sequential probing made N peers
            # cost N× wall time, and each probe's reply includes its
            # own duration_ms so skew between peers is visible.
            import threading as _threading

            from ..parallel.peer import measure_netperf
            try:
                probe = int(q1.get("bytes", str(4 << 20)))
            except ValueError:
                return send_json({"error": "bytes must be an integer"},
                                 400) or True
            probe = max(1, min(probe, 8 << 20))   # cap the probe blob
            clients = getattr(getattr(srv, "peers", None), "clients", [])
            out = [None] * len(clients)

            def _probe_one(i, c):
                t0 = time.perf_counter()
                try:
                    out[i] = measure_netperf(c, probe)
                except Exception as e:  # noqa: BLE001 — peer down
                    out[i] = {"endpoint": c.endpoint, "error": str(e),
                              "duration_ms": round(
                                  (time.perf_counter() - t0) * 1e3, 2)}

            threads = [_threading.Thread(target=_probe_one,
                                         args=(i, c), daemon=True,
                                         name=f"mt-admin-netperf-{i}")
                       for i, c in enumerate(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            return send_json({"peers": [
                r if r is not None
                else {"endpoint": c.endpoint, "error": "timeout"}
                for r, c in zip(out, clients)]}) or True
    except (KeyError, json.JSONDecodeError) as e:
        return send_json({"error": f"bad request: {e}"}, 400) or True
    except (NoSuchUser, NoSuchPolicy) as e:
        return send_json({"error": str(e)}, 404) or True
    except IAMError as e:
        return send_json({"error": str(e)}, 400) or True
    from ..s3.server import S3Error
    raise S3Error("MethodNotAllowed")


def _rebalancer(srv):
    """The attached rebalance plane, if any — duck-typed the same way
    reload_background_config finds it: the background service carrying
    a ``bandwidth_bps`` knob (an explicit ``srv.rebalancer`` wins)."""
    rb = getattr(srv, "rebalancer", None)
    if rb is not None:
        return rb
    for svc in getattr(srv, "_background", []):
        if hasattr(svc, "bandwidth_bps"):
            return svc
    return None


def _pool_arg(q1):
    """?pool= accepts an index or a pool (deployment) id; indices are
    all-digit strings, ids are uuids — never ambiguous."""
    p = q1["pool"]
    return int(p) if p.isdigit() else p


def _merge_pool_usage(srv, pools: list) -> None:
    """Fold the crawler's per-pool usage (bytes/objects) into
    pool-status rows, matched by pool id.  Best-effort: a deployment
    that never ran a scan just lacks the usage keys."""
    try:
        from ..background.crawler import load_usage
        info = load_usage(srv.layer)
    except Exception:   # noqa: BLE001 — degraded system volume
        return
    pu = getattr(info, "pools_usage", None) if info is not None else None
    if not pu:
        return
    for row in pools:
        u = pu.get(row.get("id", ""))
        if u:
            row["usedBytes"] = u.get("bytes", 0)
            row["objects"] = u.get("objects", 0)


def _drive_paths(srv) -> list:
    """Local drive roots across pools/sets (for healthinfo probes);
    the traversal lives with the selftest probes that share it."""
    from ..obs.selftest import local_drive_paths
    return local_drive_paths(srv.layer)


def _node_system_info(srv) -> dict:
    """The live-process section of a health/OBD document: flight-ring
    stats, breaker/governor state, forensic inventory — shared by the
    local healthinfo leg and the peer RPC so the merged cluster
    document is shape-identical per node."""
    from ..obs.flightrec import system_snapshot
    fx = getattr(srv, "forensic", None)
    rec = getattr(srv, "flightrec", None)
    return {
        **system_snapshot(brief=True),
        "flightrec": rec.stats() if rec is not None else None,
        "forensics": {"bundles": fx.bundles(), "dumped": fx.dumped}
        if fx is not None else None,
    }


def _xray_params(q1) -> dict:
    """Defensive query parsing for the xray filters — ONE parse shared
    by the local leg and the peer fan-out, so a malformed ?n= can
    never 500 only on clustered servers."""
    try:
        limit = max(1, min(int(q1.get("n", 100) or 100), 1000))
    except (TypeError, ValueError):
        limit = 100
    try:
        min_ms = float(q1.get("min-duration-ms", 0) or 0)
    except (TypeError, ValueError):
        min_ms = 0.0
    return {"api": q1.get("api", ""), "min_duration_ms": min_ms,
            "errors_only": q1.get("errors") == "true", "limit": limit,
            "snapshot": q1.get("snapshot") == "true"}


def _trace_tree_params(q1) -> dict:
    """One parse shared by the local leg and the peer fan-out (the
    _xray_params discipline)."""
    from ..obs import tracetree as _tt
    try:
        limit = max(1, min(int(q1.get("n", _tt.DEFAULT_TREES)
                               or _tt.DEFAULT_TREES), _tt.MAX_TREES))
    except (TypeError, ValueError):
        limit = _tt.DEFAULT_TREES
    try:
        min_ms = float(q1.get("min-duration-ms", 0) or 0)
    except (TypeError, ValueError):
        min_ms = 0.0
    return {"rid": q1.get("rid", ""), "api": q1.get("api", ""),
            "min_duration_ms": min_ms,
            "errors_only": q1.get("errors") == "true", "limit": limit}


def xray_reply(srv, api: str = "", min_duration_ms: float = 0.0,
               errors_only: bool = False, limit: int = 100,
               snapshot: bool = False) -> dict:
    """One node's xray reply — THE builder; the admin route and the
    peer RPC both call it, so the per-node shapes can never drift
    (the _node_system_info discipline)."""
    rec = getattr(srv, "flightrec", None)
    try:
        limit = max(1, min(int(limit), 1000))
    except (TypeError, ValueError):
        limit = 100
    out = {
        "node": srv.node_name,
        "stats": rec.stats() if rec is not None else None,
        "records": rec.query(api=api, min_duration_ms=min_duration_ms,
                             errors_only=errors_only, limit=limit)
        if rec is not None else [],
    }
    if rec is not None and snapshot:
        out["snapshot"] = rec.snapshot_now(brief=True)
    return out


def forensic_inventory(srv) -> dict:
    """One node's forensic-bundle inventory — shared by the admin
    ``forensics`` route and the peer RPC."""
    fx = getattr(srv, "forensic", None)
    return {"node": srv.node_name,
            "dir": fx.dir if fx is not None else "",
            "bundles": fx.bundles() if fx is not None else [],
            "dumped": fx.dumped if fx is not None else 0}


def _render_local(srv, node=None) -> str:
    """One node's scrape with every live subsystem attached — THE
    render call (plain scrape, federated local leg, and the peer RPC
    all go through here, so a newly scraped subsystem can never be
    present in one document shape and missing from another)."""
    return metrics.render(
        srv.layer, healer=getattr(srv, "healer", None),
        config=getattr(srv, "config", None),
        api_stats=getattr(srv, "api_stats", None),
        replication=getattr(srv, "replication", None),
        crawler=getattr(srv, "crawler", None), node=node,
        egress=getattr(srv, "egress", None),
        mrf=getattr(srv, "mrf", None),
        flightrec=getattr(srv, "flightrec", None),
        rebalancer=_rebalancer(srv),
        watchdog=getattr(srv, "watchdog", None),
        metering=getattr(srv, "metering", None))


def _history_params(q1) -> dict:
    """metrics-history query knobs (shared by the route and the
    parameters it forwards to every peer)."""
    from ..utils.kvconfig import parse_duration
    return {"family": q1.get("family", ""),
            "window_s": parse_duration(q1.get("window") or "30m",
                                       1800.0),
            "step_s": parse_duration(q1.get("step") or "1m", 60.0),
            "agg": q1.get("agg") or "last"}


def history_doc(srv, family: str = "", window_s: float = 1800.0,
                step_s: float = 60.0, agg: str = "last",
                node=None) -> str:
    """One node's history leg — shared by the local route and the
    ``history_query`` peer RPC so the shapes can never drift.  A
    disabled watchdog yields an empty document (the node still shows
    up via its ``mt_node_history_ok`` mark)."""
    from ..obs.history import render_history
    wd = getattr(srv, "watchdog", None)
    if wd is None:
        return ""
    text = render_history(wd.history, family=family,
                          window_s=window_s, step_s=step_s, agg=agg)
    if node and text:
        text = metrics._with_server_label(text, node)
    return text


def alerts_reply(srv) -> dict:
    """One node's alerts leg — shared by the admin route and the
    ``alerts_query`` peer RPC."""
    wd = getattr(srv, "watchdog", None)
    out = {"node": srv.node_name, "enabled": wd is not None}
    out.update(wd.alerts() if wd is not None
               else {"active": [], "recent": [], "rules": []})
    return out


_CLUSTER_SCRAPE_TTL_S = 2.0


def _metrics_cluster(srv, q1) -> str:
    """``metrics?scope=cluster``: scrape every peer in parallel
    (bounded timeout), merge into one exposition document.  Every
    sample carries a ``server`` label; a downed peer increments
    ``mt_node_scrape_errors_total`` and is marked
    ``mt_node_scrape_ok 0`` instead of failing (or silently thinning)
    the scrape — Prometheus federation's honor-the-source-labels
    contract.

    The metrics listener is unauthenticated (Prometheus convention),
    so the cluster fan-out is SINGLE-FLIGHT with a short cache: an
    anonymous request loop costs the cluster at most one fan-out per
    TTL instead of N RPC threads per request (amplification guard)."""
    cache = getattr(srv, "_cluster_scrape_cache", None)
    if cache is None:
        import threading as _threading
        cache = srv._cluster_scrape_cache = {
            "mu": _threading.Lock(), "ts": 0.0, "text": ""}
    with cache["mu"]:       # single-flight: concurrent scrapes queue
        now = time.monotonic()
        if cache["text"] and now - cache["ts"] < _CLUSTER_SCRAPE_TTL_S:
            return cache["text"]
        try:
            # floor too: a near-zero caller timeout would fail every
            # peer call on this unauthenticated route by construction
            timeout_s = min(max(float(q1.get("timeout", 10) or 10),
                                1.0), 15.0)
        except ValueError:
            timeout_s = 10.0
        peers = getattr(srv, "peers", None)
        peer_docs = []
        status = []                   # (server, ok) for scrape marks
        if peers is not None and peers.clients:
            for ep, reply, err in peers.call_all("metrics_render",
                                                 timeout_s=timeout_s):
                doc, name = None, ep
                if isinstance(reply, dict):
                    doc, name = reply.get("doc"), reply.get("node", ep)
                elif isinstance(reply, str):    # pre-PR peer shape
                    doc = reply
                if err or not isinstance(doc, str):
                    # counted BEFORE the local render so the error
                    # shows up in the scrape that observed the failure
                    metrics.GLOBAL.inc("mt_node_scrape_errors_total",
                                       {"peer": ep})
                    status.append((name, 0))
                else:
                    peer_docs.append(doc)
                    status.append((name, 1))
        local = _render_local(srv, node=srv.node_name)
        doc = metrics.merge_expositions([local] + peer_docs)
        lines = ["# TYPE mt_node_scrape_ok gauge"]
        for server, ok in [(srv.node_name, 1)] + status:
            esc = metrics._escape_label(server)
            lines.append(f'mt_node_scrape_ok{{server="{esc}"}} {ok}')
        text = doc + "\n".join(lines) + "\n"
        cache["ts"], cache["text"] = time.monotonic(), text
        return text


def background_status(srv) -> dict:
    """Live progress of the autonomous planes (madmin BgHealState /
    `mc admin scanner status` role): per-plane current bucket/object,
    objects/s + bytes/s, and ETA from the last cycle's rates.  Shared
    by the admin ``background-status`` route and the peer RPC."""
    healer = getattr(srv, "healer", None)
    crawler = getattr(srv, "crawler", None)
    repl = getattr(srv, "replication", None)
    mrf = getattr(srv, "mrf", None)
    rb = _rebalancer(srv)
    return {
        "healing": {"progress": healer.progress.snapshot(),
                    "stats": healer.stats.to_dict()}
        if healer is not None else None,
        "scanner": {"progress": crawler.progress.snapshot(),
                    "cycles": crawler.cycles}
        if crawler is not None else None,
        "replication": {"progress": repl.progress.snapshot(),
                        "stats": repl.stats.to_dict(),
                        "bandwidth": repl.monitor.report()}
        if repl is not None else None,
        "mrf": {"progress": mrf.progress.snapshot(),
                "stats": mrf.stats.to_dict()}
        if mrf is not None else None,
        "rebalance": rb.status() if rb is not None else None,
    }


def _write_chunk(h, data: bytes) -> None:
    """One HTTP/1.1 chunked-encoding frame (shared by every streaming
    admin route: trace/log streams and the speedtests)."""
    h.wfile.write(f"{len(data):x}\r\n".encode())
    h.wfile.write(data + b"\r\n")
    h.wfile.flush()


def _end_chunks(h) -> None:
    try:
        h.wfile.write(b"0\r\n\r\n")
    except (BrokenPipeError, ConnectionResetError):
        pass


def _node_dump_name(filename: str, node: str) -> str:
    """``profile-cpu.txt`` + node -> ``profile-cpu.<node>.txt`` — the
    reference's per-node profile naming inside the cluster zip."""
    node = node.removeprefix("http://").removeprefix("https://") \
        .replace("/", "_")
    stem, dot, ext = filename.rpartition(".")
    if not dot:
        return f"{filename}.{node}"
    return f"{stem}.{node}.{ext}"


def _speedtest(h, srv, route, q1) -> bool:
    """The three cluster speedtests (cmd/admin-handlers.go
    SpeedtestHandler / DriveSpeedtestHandler): run the local probe,
    fan the same probe to every peer in parallel, and STREAM one JSON
    line per node as results land, closing with a BENCH_*.json-shaped
    aggregate record ({metric, value, unit, detail}) so admin-API and
    bench-harness numbers are directly comparable."""
    import json as _json

    from ..obs import selftest

    def _num(key, default, lo, hi, cast=int):
        try:
            v = cast(q1.get(key, default))
        except (TypeError, ValueError):
            v = default
        return max(lo, min(v, hi))

    h.send_response(200)
    h.send_header("Content-Type", "application/json")
    h.send_header("Transfer-Encoding", "chunked")
    h.end_headers()
    results = []

    def emit(doc):
        results.append(doc)
        try:
            _write_chunk(h, _json.dumps(doc).encode() + b"\n")
        except (BrokenPipeError, ConnectionResetError):
            pass        # keep measuring; the caller went away

    def fan(method: str, timeout_s: float, **kwargs):
        if srv.peers is None or q1.get("local") == "true":
            return
        # non-idempotent: a replayed probe re-runs the whole measured
        # load on the peer, mid-measurement
        for ep, r, err in srv.peers.call_all_iter(
                method, timeout_s=timeout_s, idempotent=False,
                **kwargs):
            emit({"node": ep, "error": err} if err or r is None else r)

    def ok_results():
        return [r for r in results if "error" not in r]

    try:
        if route == "speedtest":
            size = _num("size", 1 << 20, 4096, 64 << 20)
            duration = _num("duration", 1.0, 0.05, 30.0, cast=float)
            concurrency = _num("concurrency", 0, 0, 64)
            local = selftest.object_speedtest(
                srv.layer, size=size, duration_s=duration,
                concurrency=concurrency)
            local["node"] = srv.node_name
            emit(local)
            # autotune runs up to 6 doubling rounds of 2 phases each
            fan("speedtest_object", max(30.0, duration * 16),
                size=size, duration_s=duration,
                concurrency=concurrency)
            ok = ok_results()
            agg = selftest.aggregate(ok, ("putGiBps", "getGiBps"))
            emit(selftest.bench_record(
                "object_put_get_GiBps", agg["putGiBps"], {
                    "putGiBps": agg["putGiBps"],
                    "getGiBps": agg["getGiBps"],
                    "objectSize": size,
                    "durationSeconds": duration,
                    "concurrency": max(
                        (r.get("concurrency", 0) for r in ok),
                        default=0),
                    "autotuned": any(r.get("autotuned") for r in ok),
                    "nodes": ok,
                    "errors": [r for r in results if "error" in r],
                }))
        elif route == "speedtest-drive":
            file_size = _num("size", 4 << 20, 1 << 16, 256 << 20)
            local = {"node": srv.node_name,
                     "drives": selftest.drive_speedtest(
                         selftest.local_drive_paths(srv.layer),
                         file_size=file_size)}
            emit(local)
            fan("speedtest_drive", 60.0, file_size=file_size)
            drives = [d for r in ok_results()
                      for d in r.get("drives", [])]
            agg = selftest.aggregate(drives,
                                     ("writeGiBps", "readGiBps"))
            emit(selftest.bench_record(
                "drive_seq_write_GiBps", agg["writeGiBps"], {
                    "writeGiBps": agg["writeGiBps"],
                    "readGiBps": agg["readGiBps"],
                    "fileSize": file_size,
                    "driveCount": len(drives),
                    "nodes": ok_results(),
                    "errors": [r for r in results if "error" in r],
                }))
        else:   # speedtest-tpu
            size = _num("size", 4 << 20, 1 << 16, 256 << 20)
            k = _num("k", 4, 1, 128)
            m = _num("m", 2, 1, 128)
            block_size = _num("blocksize", 1 << 20, 1 << 12, 16 << 20)
            local = selftest.tpu_codec_speedtest(
                size=size, k=k, m=m, block_size=block_size)
            local["node"] = srv.node_name
            emit(local)
            fan("speedtest_tpu", 60.0, size=size, k=k, m=m,
                block_size=block_size)
            ok = ok_results()
            agg = selftest.aggregate(ok, ("encodeGiBps", "decodeGiBps"))
            emit(selftest.bench_record(
                f"tpu_codec_encode_decode_GiBps_{k}+{m}",
                min(agg["encodeGiBps"], agg["decodeGiBps"]), {
                    "encode_GiBps": agg["encodeGiBps"],
                    "decode_GiBps": agg["decodeGiBps"],
                    "k": k, "m": m, "blockSize": block_size,
                    "bytes": size,
                    "nodes": ok,
                    "errors": [r for r in results if "error" in r],
                }))
    except Exception as e:  # noqa: BLE001 — surface inside the stream;
        # the 200 + chunked header is already committed
        emit({"error": f"{type(e).__name__}: {e}"})
    _end_chunks(h)
    return True


def _trace_type_filter(q1):
    """(predicate, wanted-set) from ?type= (comma-separated; default
    http-only — the pre-deep-tracing contract).  ``type=all`` streams
    every span type (predicate and set both None)."""
    want = {t for t in (q1.get("type") or "http").replace(" ", "")
            .lower().split(",") if t}
    if not want:
        want = {"http"}     # "type=," / "type= ": the default, not a
                            # match-nothing stream
    if "all" in want:
        return None, None
    return (lambda item: item.get("type", "http") in want), want


def metering_top_reply(srv) -> dict:
    """One node's ``top`` v2 attribution sections — shared by the
    local route leg and the ``metering_top`` peer RPC so the shapes
    can never drift.  {} when the plane is disabled on this node."""
    m = getattr(srv, "metering", None)
    return m.top_doc() if m is not None else {}


def _top(srv) -> dict:
    """madmin TopAPIs/TopDrives analog: hottest S3 APIs and slowest
    drives over the last-minute windows, slow-drive verdicts included."""
    from ..obs.lastminute import drive_windows, top_entries
    from ..storage.health import slow_drive_knobs, slow_drives_for_layer
    apis = top_entries(getattr(srv, "api_stats", None)) \
        if getattr(srv, "api_stats", None) is not None else []
    disks = metrics._collect_disks(srv.layer)
    multiple, min_samples = slow_drive_knobs(getattr(srv, "config", None))
    verdicts = slow_drives_for_layer(srv.layer, multiple=multiple,
                                     min_samples=min_samples)
    drives = []
    for endpoint, w in drive_windows(disks).items():
        totals = w.totals()
        count = sum(c for c, _, _ in totals.values())
        if not count:
            continue
        total_ns = sum(t for _, t, _ in totals.values())
        v = verdicts.get(endpoint, {})
        drives.append({
            "drive": endpoint, "count": count,
            "avg_ns": total_ns // max(count, 1),
            # the verdict already merged+sorted this drive's sample
            # rings; only recompute when it has no entry
            "p50_ns": v["p50_ns"] if v else w.p50_all(),
            "slow": bool(v.get("slow")),
            "ops": {op: {"count": c, "avg_ns": t // max(c, 1),
                         "bytes": b}
                    for op, (c, t, b) in sorted(totals.items())},
        })
    drives.sort(key=lambda d: d["p50_ns"], reverse=True)
    return {"apis": apis, "drives": drives,
            "knobs": {"slow_latency_multiple": multiple,
                      "slow_min_samples": min_samples}}


def _stream_with_peer_traces(h, srv, q1, flt=None, want=None) -> bool:
    """Cluster-wide trace stream: local hub subscription merged with a
    background poller pulling every peer's trace ring
    (cmd/admin-handlers.go:1082 TraceHandler + peerRESTMethodTrace).
    The type filter is applied at the earliest point on each leg: the
    local subscription drops unwanted items at publish, and peers are
    told the wanted types so their rings only capture/ship those."""
    import threading

    from ..utils.pubsub import PubSub
    merged = PubSub(max_queue=8000)
    stop = threading.Event()
    want_list = sorted(want) if want is not None else None

    def local_pump():
        with srv.trace_hub.subscribe(flt) as sub:
            while not stop.is_set():
                item = sub.get(timeout=0.25)
                if item is not None:
                    merged.publish(item)

    def peer_pump():
        cursors: dict[str, int] = {}   # trace_tails self-primes peers
        while not stop.wait(0.5):
            for item in srv.peers.trace_tails(cursors,
                                              types=want_list):
                merged.publish(item)

    threads = [threading.Thread(target=local_pump, daemon=True,
                                name="mt-admin-trace-local"),
               threading.Thread(target=peer_pump, daemon=True,
                                name="mt-admin-trace-peer")]
    for t in threads:
        t.start()
    try:
        return _stream(h, merged, q1, flt)
    finally:
        stop.set()


def _stream(h, hub, q1, flt=None) -> bool:
    """Chunked newline-JSON live stream from a PubSub hub — serves
    `mc admin trace` / `mc admin logs --follow`
    (cmd/admin-handlers.go:1082 TraceHandler).  ``flt`` drops items
    before they count against max-items (trace-type filtering)."""
    import json as _json
    try:
        timeout = min(float(q1.get("timeout", 10) or 10), 300.0)
        max_items = int(q1.get("max-items", 10000) or 10000)
    except ValueError:
        timeout, max_items = 10.0, 10000
    h.send_response(200)
    h.send_header("Content-Type", "application/json")
    h.send_header("Transfer-Encoding", "chunked")
    h.end_headers()
    with hub.subscribe(flt) as sub:
        try:
            for item in sub.drain(max_items, timeout):
                _write_chunk(h, _json.dumps(item).encode() + b"\n")
        except (BrokenPipeError, ConnectionResetError):
            pass
        _end_chunks(h)
    return True


def _server_info(srv) -> dict:
    """madmin ServerInfo analog (cmd/admin-handlers.go ServerInfoHandler)."""
    disks = metrics._collect_disks(srv.layer)
    dinfo = []
    for d in disks:
        if d is None:
            dinfo.append({"state": "offline"})
            continue
        try:
            info = d.disk_info()
            dinfo.append({
                "state": "ok", "endpoint": info.endpoint,
                "total": info.total, "free": info.free,
                "disk_id": info.disk_id})
        except Exception as e:  # noqa: BLE001
            dinfo.append({"state": "faulty", "error": str(e)})
    buckets = []
    try:
        buckets = [b.name for b in srv.layer.list_buckets()]
    except Exception:  # noqa: BLE001 — degraded layer: healthinfo
        pass           # still reports the node sections
    return {
        "mode": "distributed-erasure-tpu",
        "region": srv.region,
        "uptime_seconds": round(time.time() - _START, 1),
        "drives": dinfo,
        "buckets": buckets,
        "backend_version": 1,
        "codec": _codec_info(srv.layer),
    }


def _codec_info(layer) -> dict:
    """Where the bytes are coded, read back rather than assumed: the
    codec backends the erasure sets RESOLVED to, the device JAX reports
    when one of them is a device backend (platform, kind, count, kernel
    form, compile tallies and cache — ops/device.py), what the md5
    ``auto`` rung chose, and which native libraries loaded."""
    from ..hashing import md5fast
    from ..utils import nativelib
    pools = getattr(layer, "pools", None) or [layer]
    sets = [s for p in pools for s in getattr(p, "sets", [p])]
    backends = sorted({c.backend for c in (
        getattr(s, "_codec", None) for s in sets) if c is not None})
    out = {"backends": backends, "device": None,
           "md5": md5fast.backend_status(),
           "native": nativelib.status()}
    if any(b != "numpy" for b in backends):
        from ..ops import device
        out["device"] = device.describe()
    return out


def _config(h, srv, route, q1, payload, send_json) -> bool:
    parts = route.split("/")
    cfg = srv.config
    if h.command == "GET" and len(parts) == 1:
        return send_json({s: cfg.get_subsys(s)
                          for s in cfg.subsystems()}) or True
    if h.command == "GET" and len(parts) == 2:
        return send_json(cfg.get_subsys(parts[1])) or True
    if h.command == "PUT" and len(parts) == 3:
        value = payload.decode()
        if parts[1] == "storage_class" and value:
            # validate EC:N against the deployment's set size NOW, not
            # on every later PUT (a bad value would brick writes)
            from ..s3.server import _layer_set_drive_count
            from ..utils.kvconfig import parse_storage_class
            n = _layer_set_drive_count(srv.layer)
            try:
                parse_storage_class(value, n or 16)
            except ValueError as e:
                return send_json({"error": str(e)}, 400) or True
        cfg.set(parts[1], parts[2], value)
        if parts[1] == "api":
            # retune the live request plane (deadlines, pool size,
            # shed queue) without a restart
            srv.reload_api_config()
        if parts[1] == "pipeline":
            # retune the PUT data plane (pipeline depth, per-drive
            # writer queue depth, md5 lanes) on the live layer
            srv.reload_pipeline_config()
        if parts[1] == "rpc":
            # retune internode chunked streaming (stream_enable,
            # stream_chunk_bytes) on the live RPC plane
            srv.reload_rpc_config()
        if parts[1] == "codec":
            # retune the cross-request codec batcher (combining
            # window, batch bound, queue depth) on the live data plane
            srv.reload_codec_config()
        if parts[1] == "cache":
            # retune the hot-read plane (single-flight coalescing +
            # hot-object cache) on the live GET path; disabling
            # releases every cached byte back to the governor
            srv.reload_cache_config()
        if parts[1] == "commit":
            # retune the per-drive group-commit plane (group window,
            # batch bound, small-object packing threshold, segment
            # rotation) on the live write path
            srv.reload_commit_config()
        if parts[1] in ("heal", "scanner", "rebalance"):
            # retune heal/scan/rebalance IO self-pacing on the
            # attached background planes
            srv.reload_background_config()
        if parts[1] == "policy_opa":
            # swap the external policy webhook under the live IAM
            # plane (point at / away from an OPA endpoint, retune its
            # timeout) without a restart
            srv.reload_policy_config()
        if parts[1] == "forensic":
            # retune the forensic trigger engine (thresholds,
            # cooldown, bundle-dir bounds) on the live server
            srv.reload_forensic_config()
        if parts[1] == "watchdog":
            # rebuild the SLO watchdog (sampler + rule engine) live —
            # history rings reset, alert state starts clean
            srv.reload_watchdog_config()
        if parts[1] == "metering":
            # arm/retune the workload attribution plane (sketch
            # geometry, decay cadence) live; the hot-read per-key
            # admission hook follows the new plane
            srv.reload_metering_config()
        if parts[1] in ("logger_webhook", "audit_webhook",
                        "alert_webhook") \
                or parts[1].startswith("notify_"):
            # rebuild the egress targets live: repointed endpoints and
            # queue knobs apply without a restart (replaced targets
            # close; their queued records spill to their stores)
            srv.reload_egress_config()
        return send_json({"status": "ok"}) or True
    from ..s3.server import S3Error
    raise S3Error("MethodNotAllowed")


def _heal(h, srv, route, q1, send_json) -> bool:
    """Synchronous heal trigger (admin-heal-ops sequence, simplified):
    POST heal/<bucket>[/<prefix>] heals the bucket and every matching
    object; returns per-object results."""
    parts = route.split("/", 2)
    bucket = parts[1] if len(parts) > 1 else ""
    prefix = parts[2] if len(parts) > 2 else ""
    deep = q1.get("scan") == "deep"
    remove = q1.get("remove") == "true"
    results = []
    layer = srv.layer
    if not bucket:
        return send_json({"error": "bucket required"}, 400) or True
    healed_sets = layer.heal_bucket(bucket) \
        if hasattr(layer, "heal_bucket") else 0
    out = layer.list_objects(bucket, prefix=prefix, max_keys=10000)
    for oi in out.objects:
        try:
            r = layer.heal_object(bucket, oi.name, deep=deep,
                                  remove_dangling=remove)
            results.append({
                "object": oi.name, "before_ok": r.before_ok,
                "after_ok": r.after_ok, "healed": r.healed_disks,
                "dangling_purged": r.dangling_purged})
        except Exception as e:  # noqa: BLE001
            results.append({"object": oi.name, "error": str(e)})
    return send_json({"bucket": bucket, "bucket_sets_healed": healed_sets,
                      "objects": results}) or True


def _policy(h, srv, route, payload, send_json) -> bool:
    parts = route.split("/", 1)
    if h.command == "GET" and len(parts) == 1:
        return send_json({"policies": srv.iam.list_policies()}) or True
    name = parts[1]
    if h.command == "GET":
        return send_json(json.loads(srv.iam.get_policy(name).to_json())) \
            or True
    if h.command == "PUT":
        srv.iam.set_policy(name, iampol.Policy.from_json(payload))
        return send_json({"status": "ok"}) or True
    if h.command == "DELETE":
        srv.iam.delete_policy(name)
        return send_json({"status": "ok"}) or True
    from ..s3.server import S3Error
    raise S3Error("MethodNotAllowed")
