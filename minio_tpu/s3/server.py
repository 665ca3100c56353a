"""S3 API HTTP server (cmd/api-router.go:82 + cmd/object-handlers.go /
cmd/bucket-handlers.go).

Path-style S3 over a threading HTTP server: the L1/L3 frontend of the
framework.  Handlers authenticate (SigV4 header or presigned), map the
route to an ObjectLayer call, and render S3 XML.  The compute-heavy body
(erasure encode/decode) happens inside the object layer on TPU.
"""

from __future__ import annotations

import datetime
import email.utils
import hashlib
import os
import re
import threading
import time
import urllib.parse
import uuid
import xml.etree.ElementTree as ET
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..objectlayer import interface as ol
from ..objectlayer.bucket_meta import BucketMetadataSys
from ..obs import stages as _stages
from . import errors as s3err
from . import sigv4

MAX_OBJECT_SIZE = 5 * 1024 * 1024 * 1024 * 1024  # 5 TiB (docs/minio-limits.md)
MAX_PUT_SIZE = 5 * 1024 * 1024 * 1024   # single PUT / part (minio-limits:28)
# bodies above this stream straight into the object layer (O(batch) RSS);
# smaller ones take the simpler buffered path
STREAM_PUT_THRESHOLD = 8 * 1024 * 1024
S3_NS = "http://s3.amazonaws.com/doc/2006-03-01/"

_BUCKET_RE = re.compile(r"^[a-z0-9][a-z0-9.\-]{1,61}[a-z0-9]$")


class _BodyReader:
    """Bounded socket-body reader with optional integrity checks: caps
    reads at the declared Content-Length, raises IncompleteBody when the
    peer hangs up early, and verifies sha256/md5 digests at EOF — the
    hash.Reader analog (pkg/hash) that lets PUTs stream while keeping
    the commit gated on body integrity."""

    def __init__(self, raw, total: int, sha256_hex: str | None = None,
                 md5_digest: bytes | None = None):
        self.raw = raw
        self.remaining = total
        self._sha = hashlib.sha256() if sha256_hex else None
        self._want_sha = sha256_hex
        self._md5 = hashlib.md5() if md5_digest else None
        self._want_md5 = md5_digest

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = self.remaining
        n = min(n, self.remaining)
        if n <= 0:
            return b""
        # the streaming PUT's body arrives here, under put_object_stream
        # (or on its readahead thread: async detail then): socket read
        # + digest updates are ``body_read``, as _body()'s buffered read
        with _stages.stage("body_read"):
            chunks = []
            while n > 0:
                c = self.raw.read(n)
                if not c:
                    raise S3Error("IncompleteBody")
                chunks.append(c)
                n -= len(c)
                self.remaining -= len(c)
            data = chunks[0] if len(chunks) == 1 else b"".join(chunks)
            if self._sha is not None:
                self._sha.update(data)
            if self._md5 is not None:
                self._md5.update(data)
        if self.remaining == 0:
            if self._sha is not None and \
                    self._sha.hexdigest() != self._want_sha:
                raise S3Error("BadDigest")
            if self._md5 is not None and \
                    self._md5.digest() != self._want_md5:
                raise S3Error("BadDigest")
        return data

    def readline(self, limit: int = 8192) -> bytes:
        """Bounded readline for aws-chunked frame headers."""
        out = bytearray()
        while len(out) < limit and self.remaining > 0:
            c = self.raw.read(1)
            if not c:
                raise S3Error("IncompleteBody")
            self.remaining -= 1
            out += c
            if out.endswith(b"\r\n"):
                break
        return bytes(out)


class _MD5Reader:
    """Content-MD5 verification over an already-decoded stream (the
    aws-chunked plain view), checked at EOF before the commit."""

    def __init__(self, inner, want_md5: bytes):
        self.inner = inner
        self._md5 = hashlib.md5()
        self._want = want_md5
        self._checked = False

    def read(self, n: int = -1) -> bytes:
        data = self.inner.read(n)
        if data:
            self._md5.update(data)
        elif not self._checked:
            self._checked = True
            if self._md5.digest() != self._want:
                raise S3Error("BadDigest")
        return data




class S3Error(Exception):
    def __init__(self, code: str):
        super().__init__(code)
        self.api = s3err.get(code)


def _http_date(ns: int) -> str:
    return email.utils.formatdate(ns / 1e9, usegmt=True)


def _iso_date(ns: int) -> str:
    return datetime.datetime.fromtimestamp(
        ns / 1e9, datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%S.%f")[:-3] + "Z"


def _xml(root: ET.Element) -> bytes:
    return (b'<?xml version="1.0" encoding="UTF-8"?>' + ET.tostring(root))


def _parse_duration(s: str) -> float:
    """'10s' / '2m' / '500ms' -> seconds (cmd/config duration keys)."""
    from ..utils.kvconfig import parse_duration
    return parse_duration(s, 10.0)


class _DeadlineRFile:
    """Per-connection read deadline plumbing (cmd/http/server.go:185
    setCtx read deadlines rebuilt for a blocking rfile).

    Two regimes share one socket timeout: between requests and while
    parsing the request line/headers, a flat ``header_timeout`` applies
    (idle + slowloris-header cutoff).  While a handler reads a BODY the
    wrapper is armed with an ABSOLUTE deadline: every read re-arms the
    socket timeout to ``min(remaining, header_timeout)``, so a client
    trickling one byte per interval cannot extend its total budget —
    the per-recv timeout shrinks to whatever of the body deadline is
    left (the slow-body watchdog)."""

    def __init__(self, raw, sock, header_timeout: float):
        self._raw = raw
        self._sock = sock
        self._header_timeout = header_timeout
        self._deadline: float | None = None

    def arm(self, budget_s: float) -> None:
        self._deadline = time.monotonic() + budget_s

    def disarm(self) -> None:
        self._deadline = None
        try:
            self._sock.settimeout(self._header_timeout)
        except OSError:
            pass    # connection already torn down

    def _tick(self) -> None:
        if self._deadline is None:
            return
        remaining = self._deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError("request body deadline exceeded")
        try:
            self._sock.settimeout(min(remaining, self._header_timeout))
        except OSError:
            pass

    def read(self, n: int = -1) -> bytes:
        if self._deadline is None:
            return self._raw.read(n)
        # armed: one plain read(n) would loop on recv INSIDE the
        # buffered reader — a client trickling bytes under the per-recv
        # timeout would never surface the absolute deadline.  read1
        # issues at most one syscall, so every recv is preceded by a
        # deadline check and capped at the remaining budget.
        out = bytearray()
        want = n if n is not None and n >= 0 else None
        while want is None or len(out) < want:
            self._tick()
            chunk = self._raw.read1(
                65536 if want is None else want - len(out))
            if not chunk:
                break
            out += chunk
        return bytes(out)

    def readline(self, limit: int = -1) -> bytes:
        self._tick()
        return self._raw.readline(limit)

    def readinto(self, b) -> int:
        self._tick()
        return self._raw.readinto(b)

    def close(self) -> None:
        self._raw.close()

    @property
    def closed(self):
        return self._raw.closed

    def __getattr__(self, name):
        return getattr(self._raw, name)


def _try(fn):
    """Run a config parser, translating its ValueError into an S3Error
    (carrying the parser's .code when present)."""
    try:
        return fn()
    except ValueError as e:
        raise S3Error(getattr(e, "code", "MalformedXML")) from e


def _canned_acl_xml() -> bytes:
    """The fixed FULL_CONTROL owner ACL MinIO reports
    (cmd/bucket-handlers.go GetBucketACLHandler)."""
    root = ET.Element("AccessControlPolicy", xmlns=S3_NS)
    owner = ET.SubElement(root, "Owner")
    ET.SubElement(owner, "ID").text = "minio-tpu"
    acl = ET.SubElement(root, "AccessControlList")
    grant = ET.SubElement(acl, "Grant")
    grantee = ET.SubElement(
        grant, "Grantee",
        {"xmlns:xsi": "http://www.w3.org/2001/XMLSchema-instance",
         "xsi:type": "CanonicalUser"})
    ET.SubElement(grantee, "ID").text = "minio-tpu"
    ET.SubElement(grant, "Permission").text = "FULL_CONTROL"
    return _xml(root)


class S3Server:
    """Wires an ObjectLayer + credentials into an HTTP server."""

    def __init__(self, object_layer, access_key: str = "minioadmin",
                 secret_key: str = "minioadmin", region: str = "us-east-1",
                 host: str = "127.0.0.1", port: int = 0,
                 max_body_size: int = 1024 ** 3, iam=None, tls=None):
        self.layer = object_layer
        if iam is None:
            from ..iam.sys import IAMSys
            iam = IAMSys(object_layer, access_key, secret_key)
        self.iam = iam
        self.region = region
        self.max_body_size = max_body_size
        self.bucket_meta = BucketMetadataSys(object_layer)
        from ..utils.kvconfig import Config
        # config persists SEALED under the admin secret
        # (cmd/config-encrypted.go; secure/configcrypt.py) — plaintext
        # found on disk migrates at load, rotation re-seals in place
        self.config = Config(object_layer, secret=secret_key)
        # TLS front (secure/certs.py): an explicit CertManager wins;
        # otherwise the ``tls`` kvconfig subsystem (certs_dir layout)
        # arms it at boot.  Cert ROTATION is live via the manager's
        # mtime watcher; the handshake completes per connection in the
        # handler thread (never the accept loop).
        if tls is None:
            from ..secure.certs import CertManager
            tls = CertManager.from_config(self.config)
        self.tls = tls
        if tls is not None:
            # scheme-aware clients (S3Client/AdminClient on https
            # endpoints, the soak scrape) resolve the CA pin through
            # the process-global registry
            from ..secure import transport as _tls_transport
            _tls_transport.configure(tls)
        # etcd coordination backend (cmd/etcd.go): when configured, IAM
        # persists to etcd (cmd/iam-etcd-store.go) and federation DNS
        # records use the CoreDNS/skydns layout
        from ..utils import etcd as etcd_mod
        self.etcd = etcd_mod.from_config(self.config)
        if self.etcd is not None:
            self.iam.attach_etcd(self.etcd,
                                 self.config.get("etcd", "path_prefix"))
        from ..events import NotificationSys
        self.events = NotificationSys(self.bucket_meta, region=region)
        # wired in by server_main / tests when those subsystems are enabled
        self.replication = None  # ReplicationSys (minio_tpu/background)
        # quota's usage view (background/crawler.py UsageCache): the
        # last persisted crawler snapshot + a lock-cheap in-flight byte
        # delta.  Always attached, so hard bucket quotas enforce with
        # or without a running crawler (the cache lazily re-reads the
        # persisted usage.json when no cycle refreshes it).
        from ..background.crawler import UsageCache
        self.usage = UsageCache(object_layer)
        self.healer = None       # BackgroundHealer sweep
        self.crawler = None      # Crawler (scanner plane)
        self.mrf = None          # MRFQueue
        self.tracker = None      # DataUpdateTracker (crawler bloom filter)
        from ..crypto.kms import kms_from_env
        self.kms = kms_from_env(object_layer)
        from ..iam.openid import OpenIDProvider
        self.openid = OpenIDProvider.from_config(self.config)
        from ..iam.ldap import LDAPConfig, LDAPIdentity
        _lcfg = LDAPConfig.from_config(self.config)
        self.ldap = LDAPIdentity(_lcfg) if _lcfg.enabled else None
        # ILM tiering (cmd/bucket-lifecycle.go transitionObject): tier
        # registry persisted in the system volume
        from ..objectlayer.tiering import TransitionSys
        from ..storage.xl_storage import SYS_DIR
        blobs, _ = object_layer._fanout(
            lambda d: d.read_all(SYS_DIR, "tiers/tiers.json"))
        blob = next((b for b in blobs if b), None)
        self.transition = TransitionSys.from_json(object_layer, blob) \
            if blob else TransitionSys(object_layer)
        # observability (cmd/http-tracer.go, cmd/logger/audit.go):
        # trace hub is process-global (mirrors globalHTTPTrace); audit
        # log is per-server so deployments keep entries separate
        from ..obs import audit as _obs_audit
        from ..obs import lastminute as _obs_lastminute
        from ..obs import logger as _obs_logger
        from ..obs import trace as _obs_trace
        self.trace_hub = _obs_trace.HTTP_TRACE
        self.audit = _obs_audit.AuditLog()
        self.logger = _obs_logger.GLOBAL
        self.node_name = f"{host}:{port}"
        # last-minute per-API stats (cmd/last-minute.go role): feeds the
        # mt_s3_api_last_minute_* scrape families and the admin `top`
        # endpoint (hottest APIs)
        self.api_stats = _obs_lastminute.OpWindows(self.node_name)
        # telemetry egress plane (obs/egress.py): every config-driven
        # delivery target — logger/audit webhooks, the notify webhook,
        # broker targets — lives in this registry so the scrape, the
        # admin `targets` routes, and shutdown all see the same set
        from ..obs.egress import EgressRegistry
        self.egress = EgressRegistry()
        self._egress_owned = []
        # serializes reloads: two concurrent admin SetConfigKV calls
        # must not both tear down / rebuild the same target set
        # (duplicate registrations would leak unreachable senders)
        self._egress_reload_mu = threading.Lock()
        self.reload_egress_config()
        if self.config.get("compression", "enable") == "on":
            # build/load the native codec BEFORE serving so the first
            # request never blocks on a compile, and say which engine runs
            from .. import compress as mtc
            import logging
            if not mtc.native_available():
                logging.getLogger("minio_tpu").warning(
                    "native snappy codec unavailable; using the pure-"
                    "Python fallback (slow)")
        # live connections, so stop() can sever parked keep-alive
        # handlers instead of leaving zombie threads serving a
        # "stopped" server; _active_conns is the subset currently
        # INSIDE a request — the graceful drain lets those finish
        # while idle keep-alive parkers are severed immediately
        self._conns: set = set()
        self._active_conns: set = set()
        self._conns_mu = threading.Lock()
        # soak-plane status (minio_tpu/soak/report.py SoakStatus):
        # attached by a running soak conductor, read by admin soak-status
        self.soak = None
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.httpd.daemon_threads = True
        # severed keep-alives (shutdown drain, chaos) raise transport
        # errors in handler threads; drop them instead of printing a
        # traceback per connection
        from ..parallel.rpc import _quiet_connection_errors
        self.httpd.handle_error = _quiet_connection_errors(
            self.httpd.handle_error)
        if self.tls is not None:
            from ..secure.certs import enable_server_tls
            enable_server_tls(self.httpd, self.tls, "s3")
        self.port = self.httpd.server_address[1]
        # span attribution names the BOUND port (ephemeral binds resolve
        # only now); run_node overrides both with the cluster node_id
        self.node_name = f"{host}:{self.port}"
        self.api_stats.label = self.node_name
        _obs_trace.set_node_name(self.node_name)
        # federation binds the *actual* port (ephemeral binds resolve
        # only once the listener exists)
        from ..utils.fed_dns import FederationSys
        self.federation = FederationSys.from_config(
            self.config, host or "127.0.0.1", self.port)
        self._thread: threading.Thread | None = None
        # set by admin service?action=stop so a node-mode main thread
        # parked on it can finish shutdown (RPC plane + process exit)
        self.shutdown = threading.Event()
        # peer control-plane notifier (cluster mode; parallel/peer.py)
        self.peers = None
        # request admission throttle (cmd/handler-api.go:29-40
        # requestsPool/requestsDeadline; config keys cmd/config/api):
        # bounds concurrent S3 requests; excess waits up to the deadline
        # then gets 503 SlowDown instead of piling up threads
        self._req_waiters = 0
        self._req_waiters_mu = threading.Lock()
        self._req_max = 0
        self.reload_api_config()
        # apply persisted ``pipeline`` knobs to the layer (it booted
        # with env/defaults before this server's config existed)
        self.reload_pipeline_config()
        # push ``rpc`` streaming knobs into the shared internode plane
        self.reload_rpc_config()
        # push ``codec`` batching knobs into the shared batcher
        self.reload_codec_config()
        # push ``commit`` group-commit knobs into the shared commit
        # plane (group window, packing threshold)
        self.reload_commit_config()
        # push ``cache`` hot-read knobs into every leaf layer's plane
        # and wire the admission heat source to this server's
        # last-minute API stats
        self.reload_cache_config()
        # push ``heal``/``scanner`` pacing into attached background
        # planes (they may also attach later via attach_background)
        self.reload_background_config()
        # arm the external policy webhook (``policy_opa``) on the IAM
        # plane when configured
        self.reload_policy_config()
        # request X-ray + flight recorder (obs/flightrec.py): always-on
        # bounded rings of recent requests/errors/system snapshots,
        # queried by the admin ``xray`` route and dumped into forensic
        # bundles.  Per-server (like the audit log) so embedded
        # multi-node tests keep nodes apart.
        from ..obs.flightrec import FlightRecorder
        self.flightrec = FlightRecorder()
        # forensic trigger engine (obs/forensic.py): breach-shaped
        # signals snapshot the rings into a bounded bundle dir under
        # the first local drive (``forensic`` kvconfig subsystem);
        # None when disabled or no local drive exists (gateway modes)
        self.forensic = None
        self.reload_forensic_config()
        # SLO watchdog plane (obs/watchdog.py): the telemetry-history
        # sampler + burn-rate/drift rule engine over it (``watchdog``
        # kvconfig subsystem); None when disabled — the idle contract
        # means no mt-obs-history thread and no mt_alert_*/mt_history_*
        # family in the scrape
        self.watchdog = None
        self.reload_watchdog_config()
        # workload attribution plane (obs/metering.py): bounded
        # per-(bucket, api, access-key) registry + heavy-hitter
        # sketches (``metering`` kvconfig subsystem); None when
        # disabled — the idle contract means no charge branch at
        # completion-record time and no mt_bucket_*/mt_tenant_*
        # family in the scrape
        self.metering = None
        self.reload_metering_config()

    def reload_api_config(self) -> None:
        """(Re)derive the request-plane knobs from the ``api`` kvconfig
        subsystem — called at boot and after admin SetConfigKV so an
        operator can retune deadlines/limits on a live server."""
        try:
            req_max = int(self.config.get("api", "requests_max") or 0)
        except ValueError:
            req_max = 0
        if req_max <= 0:
            req_max = 16 * (os.cpu_count() or 8)   # auto sizing
        self.requests_deadline_s = _parse_duration(
            self.config.get("api", "requests_deadline") or "10s")
        if req_max != self._req_max:
            # swap, never resize: in-flight requests release to the
            # semaphore they acquired (dispatch captures the object)
            self._req_max = req_max
            self._req_sem = threading.BoundedSemaphore(req_max)
        # load shedding (cmd/handler-api.go maxClients 503 path): bound
        # the WAITING line too — when the queue is full a request is
        # shed immediately with 503 + Retry-After instead of parking
        # one more worker thread behind the semaphore
        try:
            req_queue = int(self.config.get("api", "requests_queue")
                            or 0)
        except ValueError:
            req_queue = 0
        self.requests_queue_max = req_queue if req_queue > 0 \
            else 2 * req_max
        # per-connection deadlines (cmd/http/server.go:185): header/idle
        # socket timeout + slow-body budget per request (scaled by the
        # declared size over the floor rate, so a large upload making
        # progress is never cut while a trickler cannot stall forever)
        self.read_header_timeout_s = _parse_duration(
            self.config.get("api", "read_header_timeout") or "30s")
        self.body_deadline_s = _parse_duration(
            self.config.get("api", "body_deadline") or "2m")
        try:
            self.body_min_rate_bps = int(
                self.config.get("api", "body_min_rate") or 0)
        except ValueError:
            self.body_min_rate_bps = 1 << 20
        # graceful shutdown drain: how long stop() lets in-flight
        # requests finish (after refusing new connections) before
        # severing; 0 = sever immediately (the PR-1 behavior)
        self.shutdown_drain_s = _parse_duration(
            self.config.get("api", "shutdown_drain_s") or "5s")
        # node memory governor (utils/memgov.py): watermark + retry
        # hint, and the Select scanner block size — all live-reloadable
        from ..utils import memgov as _memgov
        _memgov.GOVERNOR.load(self.config)
        try:
            self.select_block_bytes = max(
                64 * 1024,
                int(self.config.get("api", "select_block_bytes")
                    or 1 << 20))
        except ValueError:
            self.select_block_bytes = 1 << 20

    def reload_pipeline_config(self) -> None:
        """Push the ``pipeline`` kvconfig knobs (PUT pipeline depth,
        per-drive writer queue depth) into every leaf erasure layer —
        at boot and after admin SetConfigKV, so the live data plane
        retunes without a restart."""
        from ..objectlayer.metacache import leaf_layers_of
        for leaf in leaf_layers_of(self.layer):
            reload = getattr(leaf, "reload_pipeline_config", None)
            if reload is not None:
                try:
                    reload(self.config)
                except Exception:  # noqa: BLE001 — bad knob value must
                    pass           # not take the server down

    def reload_rpc_config(self) -> None:
        """Push the ``rpc`` streaming knobs (stream_enable,
        stream_chunk_bytes) into the process-wide internode streaming
        config — at boot and after admin SetConfigKV, so chunked shard
        streaming retunes on a live cluster (a fresh kvconfig.Config
        cannot see this server's dynamic layer)."""
        from ..parallel import rpc as _rpc
        try:
            _rpc.STREAM.load(self.config)
        except Exception:  # noqa: BLE001 — bad knob must not kill boot
            pass

    def reload_codec_config(self) -> None:
        """Push the ``codec`` batching knobs (enable, batch_window_us,
        max_batch_blocks, queue_depth) into the process-wide
        cross-request codec batcher — at boot and after admin
        SetConfigKV, so the combining window retunes on a live server
        (a fresh kvconfig.Config cannot see this server's dynamic
        layer)."""
        from ..parallel import batcher as _batcher
        try:
            _batcher.CONFIG.load(self.config)
        except Exception:  # noqa: BLE001 — bad knob must not kill boot
            pass

    def reload_commit_config(self) -> None:
        """Push the ``commit`` group-commit knobs (enable,
        group_window_us, max_batch, pack_threshold, segment_max_bytes)
        into the process-wide commit-plane config — at boot and after
        admin SetConfigKV, so the group window and packing threshold
        retune on a live server (a fresh kvconfig.Config cannot see
        this server's dynamic layer)."""
        from ..storage import commit as _commit
        try:
            _commit.CONFIG.load(self.config)
        except Exception:  # noqa: BLE001 — bad knob must not kill boot
            pass

    def reload_cache_config(self) -> None:
        """Push the ``cache`` kvconfig knobs (enable, max_bytes,
        heat_threshold, singleflight_queue, window_bytes) into the
        process-wide hot-read config and wire each leaf layer's plane
        to THIS server's last-minute per-API stats as its admission
        heat source — at boot and after admin SetConfigKV, so the
        hot-object cache retunes on a live server.  Disabling releases
        every cached byte back to the memory governor immediately."""
        from ..objectlayer import hotread as _hotread
        try:
            _hotread.CONFIG.load(self.config)
        except Exception:  # noqa: BLE001 — bad knob must not kill boot
            pass
        stats = self.api_stats

        def _get_heat() -> int:
            w = stats.windows.get("GetObject")
            return w.total()[0] if w is not None else 0

        # per-key admission heat: when the metering plane is armed its
        # count-min estimate gates admission per OBJECT; otherwise the
        # plane falls back to the global GetObject rate above
        metering = getattr(self, "metering", None)
        heat_key = metering.key_heat if metering is not None else None

        from ..objectlayer.metacache import leaf_layers_of
        for leaf in leaf_layers_of(self.layer):
            plane = getattr(leaf, "hotread", None)
            if plane is not None:
                plane.heat_fn = _get_heat
                plane.heat_key_fn = heat_key
                if not _hotread.CONFIG.enable:
                    plane.clear()

    def reload_policy_config(self) -> None:
        """(Re)build the external policy webhook from the
        ``policy_opa`` kvconfig subsystem and swap it under
        ``IAMSys.is_allowed`` — at boot and after admin SetConfigKV,
        so an operator can point the cluster at (or away from) an OPA
        endpoint on a live server.  An empty url restores local policy
        evaluation."""
        from ..secure.opa import OpaWebhook
        try:
            self.iam.authorizer = OpaWebhook.from_config(self.config)
        except Exception:  # noqa: BLE001 — a bad knob value must not
            pass           # take the server (or the IAM plane) down

    def reload_forensic_config(self) -> None:
        """(Re)build the forensic trigger engine from the ``forensic``
        kvconfig subsystem — at boot and after admin SetConfigKV, so
        an operator can retune thresholds/cooldowns (or disable the
        engine) on a live server.  Trigger cooldown history resets on
        reload; the bundle dir is reaped by whichever engine writes
        next."""
        from ..obs.forensic import ForensicSys
        old = getattr(self, "forensic", None)
        if old is not None:
            # the outgoing engine's in-flight bundle write finishes
            # (bounded) before the swap — a dangling mt-forensic-dump
            # thread must not write/reap the dir after a reload
            old.join(timeout=5.0)
        try:
            self.forensic = ForensicSys.from_server(self)
        except Exception:  # noqa: BLE001 — a bad knob value must not
            self.forensic = None       # take the server down

    def reload_watchdog_config(self) -> None:
        """(Re)build the SLO watchdog from the ``watchdog`` kvconfig
        subsystem — at boot and after admin SetConfigKV.  A reload
        replaces the engine wholesale: history rings reset (documented
        in the subsystem comment) and alert state starts clean."""
        from ..obs.watchdog import WatchdogSys
        old = getattr(self, "watchdog", None)
        if old is not None:
            # stop the outgoing sampler thread before the swap — two
            # mt-obs-history threads must never tick concurrently
            old.stop(timeout=5.0)
        try:
            self.watchdog = WatchdogSys.from_server(self)
        except Exception:  # noqa: BLE001 — a bad knob value must not
            self.watchdog = None       # take the server down
        if self.watchdog is not None:
            self.watchdog.start()

    def reload_metering_config(self) -> None:
        """(Re)build the workload attribution plane from the
        ``metering`` kvconfig subsystem — at boot and after admin
        SetConfigKV.  A reload replaces the registry wholesale
        (counters and sketches reset, documented in the subsystem
        comment), then re-runs the cache reload so every hot-read
        plane's per-key heat source follows the swap."""
        from ..obs.metering import Metering
        try:
            self.metering = Metering.from_server(self)
        except Exception:  # noqa: BLE001 — a bad knob value must not
            self.metering = None       # take the server down
        self.reload_cache_config()

    def reload_background_config(self) -> None:
        """Push the ``heal``/``scanner`` pacing knobs into every
        attached background plane (attach_background) — at boot and
        after admin SetConfigKV, so heal/scan IO yielding retunes on a
        live server.  Duck-typed on the pacing attributes: a healer
        exposes ``pace_s``/``deep_every``, a crawler
        ``delay_mult``/``max_wait_s``."""
        cfg = self.config
        try:
            bitrot = cfg.get("heal", "bitrotscan") == "on"
            pace = _parse_duration(cfg.get("heal", "max_sleep") or "1s")
            delay = float(cfg.get("scanner", "delay") or 0)
            max_wait = _parse_duration(
                cfg.get("scanner", "max_wait") or "15s")
            rb_enable = cfg.get("rebalance", "enable") == "on"
            rb_workers = int(cfg.get("rebalance", "max_workers") or 1)
            rb_bw = int(cfg.get("rebalance", "bandwidth") or 0)
        except (KeyError, ValueError):
            return
        for svc in getattr(self, "_background", []):
            if hasattr(svc, "bandwidth_bps"):
                # the rebalancer: its own enable/workers/bandwidth knobs
                # plus the healer's IO self-pacing cap
                svc.enabled = rb_enable
                svc.max_workers = rb_workers
                svc.bandwidth_bps = rb_bw
                svc.pace_s = pace
                svc.monitor.set_limit("rebalance", rb_bw)
                continue
            if hasattr(svc, "pace_s"):
                svc.pace_s = pace
                # bitrotscan=on forces deep sweeps; turning it back
                # off must RESTORE the constructed cadence (the
                # override is remembered so a live off actually lands)
                if bitrot and not hasattr(svc, "_bitrot_prev"):
                    svc._bitrot_prev = svc.deep_every
                    svc.deep_every = 1       # deep-scan EVERY sweep
                elif not bitrot and hasattr(svc, "_bitrot_prev"):
                    svc.deep_every = svc._bitrot_prev
                    del svc._bitrot_prev
            if hasattr(svc, "delay_mult"):
                svc.delay_mult = delay
                svc.max_wait_s = max_wait

    def reload_egress_config(self) -> None:
        """(Re)build every config-driven egress target from the
        ``logger_webhook`` / ``audit_webhook`` / ``notify_*`` kvconfig
        subsystems — called at boot and after admin SetConfigKV so an
        operator can repoint endpoints or retune queue knobs on a live
        server.  Replaced targets are closed (their queued records
        spill to their disk stores).  One bad subsystem config must not
        take the others' telemetry down: each target builds under its
        own guard, and a failure is logged and skipped."""
        with self._egress_reload_mu:
            self._reload_egress_locked()

    def _reload_egress_locked(self) -> None:
        from ..events import WebhookTarget
        from ..events.brokers import BROKER_KINDS, target_from_config
        from ..obs import logger as _obs_logger
        from ..obs.egress import config_queue_limit
        for t in getattr(self, "_egress_owned", []):
            try:
                if t in self.logger.targets:
                    self.logger.targets.remove(t)
                if t in self.audit.targets:
                    self.audit.targets.remove(t)
                if getattr(t, "arn", ""):
                    self.events.remove_target(t.arn)
                self.egress.remove(t)
                t.close()
            except Exception:  # noqa: BLE001 — a broken old target
                pass           # must not block the reload
        self._egress_owned = []
        cfg = self.config

        def _own(t):
            self.egress.register(t)
            self._egress_owned.append(t)
            return t

        for sub, sink in (("logger_webhook", self.logger.targets),
                          ("audit_webhook", self.audit.targets),
                          ("alert_webhook", None)):
            try:
                if cfg.get(sub, "enable") != "on":
                    continue
                size = config_queue_limit(cfg, sub, "queue_size")
                t = _own(_obs_logger.HTTPLogTarget(
                    cfg.get(sub, "endpoint"), cfg.get(sub, "auth_token"),
                    target_type=sub.split("_", 1)[0],
                    queue_limit=size, store_limit=size,
                    store_dir=cfg.get(sub, "queue_dir") or None))
                if sink is not None:
                    sink.append(t)
                # alert targets have no log sink: the watchdog engine
                # pushes alert events into them directly (it discovers
                # them in the egress registry by target_type)
            except Exception as e:  # noqa: BLE001 — bad subsystem config
                self.logger.error(f"egress: building {sub} target "
                                  f"failed: {e}")
        try:
            if cfg.get("notify_webhook", "enable") == "on":
                # config-driven target registration (cmd/config/notify):
                # the ARN a PUT-notification config may reference
                lim = config_queue_limit(cfg, "notify_webhook",
                                         "queue_limit")
                self.events.register_target(_own(WebhookTarget(
                    "arn:minio:sqs::1:webhook",
                    cfg.get("notify_webhook", "endpoint"),
                    auth_token=cfg.get("notify_webhook", "auth_token"),
                    store_dir=cfg.get("notify_webhook", "queue_dir")
                    or None,
                    queue_limit=lim, store_limit=lim)))
        except Exception as e:  # noqa: BLE001 — bad subsystem config
            self.logger.error(f"egress: building notify_webhook target "
                              f"failed: {e}")
        for kind in BROKER_KINDS:
            try:
                t = target_from_config(kind, cfg)
            except Exception as e:  # noqa: BLE001 — bad subsystem config
                self.logger.error(f"egress: building notify_{kind} "
                                  f"target failed: {e}")
                continue
            if t is not None:
                self.events.register_target(_own(t))

    def body_budget_s(self, content_length: int) -> float:
        """Read budget for one request body: the flat deadline plus
        declared-size / floor-rate headroom."""
        budget = self.body_deadline_s
        if content_length > 0 and self.body_min_rate_bps > 0:
            budget += content_length / self.body_min_rate_bps
        return budget

    def attach_tracker(self, tracker) -> None:
        """Wire the data-update tracker into event marking AND listing-
        cache validity (the metacache consults it instead of waiting
        out its TTL — cmd/metacache-bucket.go coupling)."""
        self.tracker = tracker
        from ..objectlayer.metacache import managers_of
        for mc in managers_of(self.layer):
            mc.tracker = tracker

    def attach_peers(self, notifier) -> None:
        """Wire the peer fan-out: IAM/bucket-metadata mutations reload on
        every node immediately (cmd/peer-rest-common.go:27-61), and the
        trace hub keeps a pollable ring for cross-node aggregation."""
        self.peers = notifier
        self.bucket_meta.on_change = notifier.bucket_meta_changed
        self.iam.on_change = notifier.iam_changed
        self.trace_hub.enable_ring()

    def attach_background(self, *services) -> None:
        """Register background loops (crawler, healer) whose lifecycle
        follows the server's: started on start(), stopped on stop()
        (initDataCrawler / initBackgroundHealing, cmd/server-main.go)."""
        self._background = getattr(self, "_background", [])
        self._background.extend(services)
        for svc in services:
            # a crawler refreshes this server's quota usage view at
            # the end of every cycle (duck-typed on the attribute so
            # test fakes without it still attach)
            if hasattr(svc, "usage_cache"):
                svc.usage_cache = self.usage
        # late attachments pick up the ``heal``/``scanner`` pacing
        # knobs the boot-time reload could not reach
        self.reload_background_config()

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True,
                                        name="mt-s3-server")
        self._thread.start()
        for svc in getattr(self, "_background", []):
            svc.start()

    def stop(self) -> None:
        self._stopping = True          # health probes report offline
        for svc in getattr(self, "_background", []):
            try:
                svc.stop()
            except Exception:  # noqa: BLE001 — shutdown must proceed
                pass
        self.httpd.shutdown()
        # graceful drain (cmd/http/server.go Shutdown analog): the
        # listener closes FIRST, so new connections are refused while
        # in-flight requests get the drain budget to finish.  Idle
        # keep-alive handlers have no request in flight — severed
        # immediately; handlers finishing a request during the drain
        # close their connection themselves (_stopping gate).
        self.httpd.server_close()
        from ..parallel.rpc import sever_connections
        drain_s = getattr(self, "shutdown_drain_s", 0.0)
        if drain_s > 0:
            with self._conns_mu:
                idle = [c for c in self._conns
                        if c not in self._active_conns]
            sever_connections(idle)
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline:
                with self._conns_mu:
                    if not self._active_conns:
                        break
                time.sleep(0.02)
        # whatever is still parked or past the drain budget dies now
        with self._conns_mu:
            conns = list(self._conns)
        sever_connections(conns)
        # watchdog down BEFORE the egress plane: the sampler thread
        # (mt-obs-history) joins so no alert event is pushed into a
        # target that is mid-close below
        if getattr(self, "watchdog", None) is not None:
            self.watchdog.stop(timeout=5.0)
        self.events.close()
        # egress plane down WITH the server: sender threads join, queued
        # records spill to their disk stores, and this server's targets
        # leave the process-global logger so a later server (or test)
        # never delivers through a dead target
        for t in getattr(self, "_egress_owned", []):
            if t in self.logger.targets:
                self.logger.targets.remove(t)
        if getattr(self, "egress", None) is not None:
            self.egress.close_all()
        # writer plane down WITH the server: per-drive writer threads
        # join, queued ops fail with PlaneClosed (in-flight PUTs abort
        # and clean their tmp files), blocked enqueuers wake.  The
        # plane reopens lazily if a shared layer serves again later.
        from ..storage.writers import close_write_planes
        close_write_planes(self.layer)
        # disk-cache layers down WITH the server: writeback + GC
        # threads (mt-diskcache-*) join so nothing outlives stop()
        from ..objectlayer.diskcache import CacheObjects
        lay, seen = self.layer, set()
        while lay is not None and id(lay) not in seen:
            seen.add(id(lay))
            if isinstance(lay, CacheObjects):
                lay.close()
            lay = lay.__dict__.get("inner")
        # hot-read plane: release every cached byte back to the memory
        # governor — a stopped node holds no resident hot tier, and the
        # process-wide inuse accounting must read zero at idle
        from ..objectlayer.metacache import leaf_layers_of
        for leaf in leaf_layers_of(self.layer):
            plane = getattr(leaf, "hotread", None)
            if plane is not None:
                plane.clear()
        # an in-flight forensic bundle write finishes (bounded) so the
        # thread-hygiene assertions never see a dangling dump worker
        if getattr(self, "forensic", None) is not None:
            self.forensic.join(timeout=10.0)
        if self.peers is not None:
            self.peers.close()

    @property
    def endpoint(self) -> str:
        scheme = "https" if self.tls is not None else "http"
        return f"{scheme}://127.0.0.1:{self.port}"

    def notify(self, event_name: str, bucket: str, oi,
               req_params: dict | None = None) -> None:
        """Fire a bucket event into the notification system."""
        if self.tracker is not None and oi is not None:
            # feed the crawler's change bloom filter on every mutation
            self.tracker.mark(bucket, getattr(oi, "name", ""))
        if self.peers is not None and oi is not None:
            # feed every PEER's tracker too: their cached listings for
            # this bucket go stale now, not after the metacache TTL
            self.peers.object_changed(bucket, getattr(oi, "name", ""))
        self.events.send(event_name, bucket, oi, req_params or {})

    def replicate(self, bucket: str, oi, delete: bool = False) -> None:
        """Queue async replication if the bucket's config asks for it
        (no-op until ReplicationSys is attached)."""
        if self.replication is not None:
            self.replication.queue(bucket, oi, delete=delete)


def _layer_set_drive_count(layer) -> int:
    """Drives per erasure set for any topology shape (storage-class
    parity is bounded by the SET size, not total drives)."""
    n = getattr(layer, "set_drive_count", 0)
    if n:
        return n
    pools = getattr(layer, "pools", None)
    if pools:
        return getattr(pools[0], "set_drive_count",  # mt-lint: ok(pool-routing) shape probe — every pool shares the set geometry, any index answers
                       0)
    return len(getattr(layer, "disks", []) or [])


def _api_name(method: str, bucket: str, key: str, q1: dict) -> str:
    """Best-effort S3 API name for traces/audit (the reference names come
    from mux route registration, cmd/api-router.go)."""
    if bucket == "minio-tpu" or not bucket:
        if method == "POST" and not bucket:
            return "STS"
        return "AdminAPI" if bucket else "ListBuckets"
    sub = {"uploads": "MultipartUpload", "uploadId": "MultipartUpload",
           "tagging": "Tagging", "retention": "Retention",
           "legal-hold": "LegalHold", "select": "SelectObjectContent",
           "versioning": "Versioning", "policy": "BucketPolicy",
           "lifecycle": "BucketLifecycle", "encryption": "BucketEncryption",
           "replication": "BucketReplication", "notification":
           "BucketNotification", "object-lock": "ObjectLockConfig",
           "versions": "ListObjectVersions", "delete": "DeleteObjects"}
    feature = next((v for k, v in sub.items() if k in q1), "")
    if key:
        base = {"GET": "GetObject", "HEAD": "HeadObject",
                "PUT": "PutObject", "DELETE": "DeleteObject",
                "POST": "PostObject"}.get(method, method)
        if feature and feature != "MultipartUpload":
            return {"GET": "Get", "PUT": "Put",
                    "DELETE": "Delete"}.get(method, "") + feature \
                if feature in ("Tagging", "Retention", "LegalHold") \
                else feature
        if feature == "MultipartUpload":
            return {"POST": "CompleteMultipartUpload"
                    if "uploadId" in q1 else "CreateMultipartUpload",
                    "PUT": "UploadPart", "GET": "ListParts",
                    "DELETE": "AbortMultipartUpload"}.get(method, base)
        return base
    base = {"GET": "ListObjectsV2" if q1.get("list-type") == "2"
            else "ListObjectsV1",
            "HEAD": "HeadBucket", "PUT": "MakeBucket",
            "DELETE": "DeleteBucket", "POST": "PostPolicyBucket"}
    if feature:
        return ({"GET": "Get", "PUT": "Put", "DELETE": "Delete"}
                .get(method, "") + feature) \
            if feature.startswith("Bucket") or feature == "Versioning" \
            else feature
    return base.get(method, method)


def _make_handler(srv: S3Server):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        server_version = "MinioTPU"

        # -- plumbing ------------------------------------------------------

        def setup(self):
            # per-connection deadlines (cmd/http/server.go:185): the
            # socket timeout covers request-line/header reads and
            # keep-alive idle; the rfile wrapper adds the absolute
            # slow-body budget, armed per request in _dispatch.
            # (header SIZE is already bounded by http.server: 64 KiB
            # per line, 100 headers max)
            self.timeout = getattr(srv, "read_header_timeout_s", None)
            if srv.tls is not None:
                # deferred TLS handshake, in THIS handler thread under
                # the header deadline (the accept loop never blocks on
                # a slow client's handshake); failure counts into
                # mt_tls_handshake_failed_total and tears down just
                # this connection
                srv.tls.handshake(self.request, "s3",
                                  timeout=self.timeout or 30.0)
            super().setup()
            self.rfile = _DeadlineRFile(self.rfile, self.connection,
                                        self.timeout or 30.0)
            with srv._conns_mu:
                srv._conns.add(self.connection)

        def finish(self):
            try:
                super().finish()
            finally:
                with srv._conns_mu:
                    srv._conns.discard(self.connection)

        def log_message(self, fmt, *args):  # quiet; tracing hooks later
            pass

        def _split(self):
            u = urllib.parse.urlsplit(self.path)
            path = urllib.parse.unquote(u.path)
            query = urllib.parse.parse_qs(u.query, keep_blank_values=True)
            parts = path.lstrip("/").split("/", 1)
            bucket = parts[0]
            key = parts[1] if len(parts) > 1 else ""
            return path, bucket, key, query

        def _body(self) -> bytes:
            n = int(self.headers.get("Content-Length") or 0)
            if n > srv.max_body_size:
                # reject before buffering: unauthenticated clients must not
                # be able to force huge allocations
                raise S3Error("EntityTooLarge")
            if not n:
                return b""
            with _stages.stage("body_read"):
                return self.rfile.read(n)

        def _auth(self, path, query, payload: bytes) -> bytes:
            self._query_token = query.get("X-Amz-Security-Token", [""])[0]
            with _stages.stage("auth"):
                out = self._auth_inner(path, query, payload)
                self._check_session_token()
            return out

        def _auth_inner(self, path, query, payload: bytes) -> bytes:
            """Authenticate; returns the effective payload (aws-chunked
            bodies are signature-verified per chunk and de-framed).  Sets
            self.access_key for authorization."""
            lookup = srv.iam.lookup_secret
            hdrs = {k: v for k, v in self.headers.items()}
            try:
                if "Authorization" not in hdrs and \
                        "X-Amz-Signature" not in query and \
                        not ("Signature" in query and
                             "AWSAccessKeyId" in query):
                    # anonymous request: authorization happens against the
                    # bucket policy alone (cmd/auth-handler.go authTypeAnonymous)
                    self.access_key = ""
                    sha = self.headers.get("x-amz-content-sha256")
                    if sha and sha != sigv4.UNSIGNED_PAYLOAD:
                        if hashlib.sha256(payload).hexdigest() != sha:
                            raise S3Error("BadDigest")
                    return payload
                auth_hdr = hdrs.get("Authorization", "")
                if auth_hdr.startswith("AWS "):
                    # Signature V2 header auth (cmd/signature-v2.go)
                    from . import sigv2
                    self.access_key = sigv2.verify_request(
                        lookup, self.command, path, query, hdrs)
                    return payload
                if "Signature" in query and "AWSAccessKeyId" in query:
                    # presigned V2
                    from . import sigv2
                    self.access_key = sigv2.verify_presigned(
                        lookup, self.command, path, query, hdrs)
                    return payload
                if "X-Amz-Signature" in query:
                    self.access_key = sigv4.verify_presigned(
                        lookup, self.command, path, query, hdrs,
                        region=srv.region)
                    return payload
                sha = self.headers.get("x-amz-content-sha256",
                                       sigv4.UNSIGNED_PAYLOAD)
                if sha == sigv4.STREAMING_PAYLOAD:
                    self.access_key, key, seed, amz_date, scope = \
                        sigv4.verify_request_streaming(
                            lookup, self.command, path, query, hdrs,
                            region=srv.region)
                    return sigv4.decode_chunked_payload(
                        payload, key, seed, amz_date, scope)
                if sha != sigv4.UNSIGNED_PAYLOAD:
                    got = hashlib.sha256(payload).hexdigest()
                    if got != sha:
                        raise S3Error("BadDigest")
                self.access_key = sigv4.verify_request(
                    lookup, self.command, path, query, hdrs, sha,
                    region=srv.region)
                return payload
            except sigv4.SigV4Error as e:
                raise S3Error(e.code) from e

        def _allow(self, action: str, resource: str = "") -> None:
            """Authorize the authenticated key for an S3 action: bucket
            policy first (explicit Deny wins, Allow grants even anonymous),
            then IAM (checkRequestAuthType -> IAMSys.IsAllowed)."""
            with _stages.stage("policy"):
                self._allow_inner(action, resource)

        def _allow_inner(self, action: str, resource: str = "") -> None:
            bucket = resource.split("/", 1)[0]
            # bucket policy can only speak for s3: actions — admin:* must
            # never be grantable by a bucket document
            if bucket and action.startswith("s3:"):
                try:
                    pol = srv.bucket_meta.get_bucket_policy(bucket)
                    verdict = pol.is_allowed(
                        self.access_key, action, resource) \
                        if pol is not None else None
                except Exception as e:  # noqa: BLE001 — fail CLOSED: an
                    # unevaluable policy must not silently drop its Denies
                    raise S3Error("AccessDenied") from e
                if verdict is False:
                    raise S3Error("AccessDenied")
                if verdict is True:
                    # a bucket-policy Allow still intersects with an STS
                    # session policy — temp creds never exceed their bound
                    if srv.iam.session_policy_allows(self.access_key,
                                                     action, resource):
                        return
                    raise S3Error("AccessDenied")
            if not self.access_key or \
                    not srv.iam.is_allowed(self.access_key, action,
                                           resource):
                raise S3Error("AccessDenied")

        def _send_prologue(self, status: int, sent_bytes: int,
                           entity_len: int, content_type: str,
                           headers: dict | None):
            """Shared response plumbing (metrics, trace bookkeeping,
            status line + common headers) for _send and _send_stream.
            sent_bytes feeds metrics (0 for HEAD); entity_len is the
            Content-Length header value."""
            from ..admin.metrics import GLOBAL as mtr
            mtr.inc("mt_s3_requests_total",
                    {"method": self.command, "status": str(status)})
            mtr.inc("mt_s3_tx_bytes_total", value=sent_bytes)
            self._resp_status = status
            self._resp_headers = dict(headers or {})
            self._resp_bytes = getattr(self, "_resp_bytes", 0) + sent_bytes
            if not getattr(self, "_ttfb_ns", 0) and \
                    getattr(self, "_t0_ns", 0):
                import time as _time
                self._ttfb_ns = _time.time_ns() - self._t0_ns
            self.send_response(status)
            self.send_header("x-amz-request-id",
                             getattr(self, "_req_id", None)
                             or uuid.uuid4().hex[:16])
            self.send_header("Server", "MinioTPU")
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(entity_len))
            self.end_headers()

        def _send(self, status: int, body: bytes = b"",
                  content_type: str = "application/xml",
                  headers: dict | None = None,
                  content_length: int | None = None):
            """content_length: explicit value for HEAD responses (body is
            not sent but the header must describe the entity)."""
            self._send_prologue(
                status, len(body),
                len(body) if content_length is None else content_length,
                content_type, headers)
            if body and self.command != "HEAD":
                with _stages.stage("body_write"):
                    self.wfile.write(body)

        def _send_stream(self, status: int, gen, total: int,
                         content_type: str, headers: dict | None = None):
            """Stream a known-length body chunk by chunk (the
            NewGetObjectReader pipeline end, cmd/object-api-utils.go:586).
            On a mid-stream failure the connection is dropped — the
            short body vs Content-Length signals truncation."""
            # pull the first chunk BEFORE committing the status line so
            # an immediately-failing read still yields a proper XML error
            it = iter(gen)
            first = b""
            if self.command != "HEAD" and total:
                try:
                    with _stages.stage("stream_wait"):
                        first = next(it)
                except StopIteration:
                    first = b""
            self._send_prologue(status, total, total, content_type,
                                headers)
            try:
                if first:
                    with _stages.stage("body_write"):
                        self.wfile.write(first)
                # pull OUTSIDE the body_write stage, under stream_wait:
                # with readahead it is this thread's wait for the
                # producer (whose drive_read/decode are async detail);
                # without, the producer's stages nest inside it and
                # take their time out — either way not socket time
                while True:
                    try:
                        with _stages.stage("stream_wait"):
                            chunk = next(it)
                    except StopIteration:
                        break
                    if chunk:
                        with _stages.stage("body_write"):
                            self.wfile.write(chunk)
            except (ConnectionError, TimeoutError):
                # the client died mid-body: propagate so the dispatch
                # abort catch stamps the completion record with the
                # ``aborted`` marker and the stage vector it already
                # accumulated (tests/test_chaos_network.py reset drill)
                self.close_connection = True
                raise
            except Exception:   # noqa: BLE001 — headers are gone; a
                # second response would corrupt the stream
                self.close_connection = True

        def _send_chunked(self, status: int, chunks, content_type: str,
                          headers: dict | None = None,
                          head: bytes = b""):
            """Stream an UNKNOWN-length body via chunked transfer
            encoding (SelectObjectContent event streams — the response
            length is only known once the scan finishes, and buffering
            it would defeat the O(block) scanner).  ``head`` is written
            first (frames accumulated before the caller decided to
            stream).  A mid-stream failure drops the connection: the
            missing terminal 0-chunk signals truncation to the client,
            the chunked-framing analog of the short-body signal in
            _send_stream."""
            from ..admin.metrics import GLOBAL as mtr
            mtr.inc("mt_s3_requests_total",
                    {"method": self.command, "status": str(status)})
            self._resp_status = status
            self._resp_headers = dict(headers or {})
            if not getattr(self, "_ttfb_ns", 0) and \
                    getattr(self, "_t0_ns", 0):
                import time as _time
                self._ttfb_ns = _time.time_ns() - self._t0_ns
            self.send_response(status)
            self.send_header("x-amz-request-id",
                             getattr(self, "_req_id", None)
                             or uuid.uuid4().hex[:16])
            self.send_header("Server", "MinioTPU")
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.send_header("Content-Type", content_type)
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def write_chunk(data: bytes):
                self.wfile.write(f"{len(data):x}\r\n".encode())
                self.wfile.write(data + b"\r\n")

            sent = 0
            try:
                if head:
                    write_chunk(head)
                    sent += len(head)
                for chunk in chunks:
                    if chunk:
                        write_chunk(chunk)
                        sent += len(chunk)
                self.wfile.write(b"0\r\n\r\n")
                self.wfile.flush()
            except (ConnectionError, TimeoutError):
                # client death mid-stream: same abort contract as
                # _send_stream — the dispatch catch records the marker
                self.close_connection = True
                raise
            except Exception:   # noqa: BLE001 — headers are gone; drop
                self.close_connection = True
            finally:
                mtr.inc("mt_s3_tx_bytes_total", value=sent)
                self._resp_bytes = getattr(self, "_resp_bytes", 0) + sent

        def _fail(self, e: Exception, resource: str = ""):
            from ..crypto.sse import SSEError
            from ..parallel.dsync import LockLost, LockTimeout
            from ..utils.memgov import MemoryPressure
            if isinstance(e, MemoryPressure):
                # governor shed: same 503 + Retry-After contract as the
                # request-pool load-shed path — clients back off and
                # retry instead of watching the node OOM
                api = s3err.get("SlowDown")
                return self._send(
                    api.http_status,
                    s3err.to_xml(api, resource,
                                 getattr(self, "_req_id", "") or ""),
                    headers={"Retry-After":
                             str(max(1, int(e.retry_after_s)))})
            if isinstance(e, S3Error):
                api = e.api
            elif isinstance(e, (SSEError, sigv4.SigV4Error)):
                api = s3err.get(e.code)
            elif isinstance(e, ol.ObjectLayerError):
                api = s3err.from_object_error(e)
            elif isinstance(e, (LockTimeout, LockLost)):
                # lock contention is congestion, not a server fault
                # (the reference maps operation timeouts to 503)
                api = s3err.get("SlowDown")
            elif isinstance(e, TimeoutError):
                # read deadline fired mid-body (slowloris cutoff):
                # 408, and the connection must drop — the unread body
                # bytes would desync keep-alive (socket.timeout is a
                # TimeoutError alias since 3.10)
                api = s3err.get("RequestTimeout")
                self.close_connection = True
            else:
                api = s3err.get("InternalError")
            self._send(api.http_status,
                       s3err.to_xml(api, resource,
                                    getattr(self, "_req_id", "") or ""))

        def _dispatch(self):
            """Trace/audit wrapper around the real dispatcher
            (cmd/http-tracer.go httpTraceAll + cmd/logger/audit.go)."""
            from ..obs import trace as _trace
            self._t0_ns = _trace.now_ns()
            # monotonic twin for durations fed into latency windows (a
            # wall-clock step must not record garbage into api_stats)
            self._t0m_ns = time.monotonic_ns()
            self._req_id = uuid.uuid4().hex[:16]
            # correlation root (Dapper-style): every subsystem span this
            # request causes — storage calls, internode RPCs, TPU
            # kernels, even on peer nodes — carries this ID.  The causal
            # tree roots at the request itself: root span id == request
            # id, and every span minted on this thread parents under it
            # until a deeper span pushes its own id
            _trace.set_request_id(self._req_id)
            _trace.set_span_parent(self._req_id)
            # X-ray stage clock, minted beside the request ID and torn
            # down with it; the completion record lands in the flight
            # ring whatever happens below
            _stages.begin()
            self._resp_status = 0
            self._resp_headers = {}
            self._resp_bytes = 0
            self._ttfb_ns = 0
            self._rx_bytes = 0
            self._abort_err = ""
            # request-pool admission (cmd/handler-api.go:29 maxClients):
            # S3 traffic only — admin/metrics/health stay reachable when
            # the data plane is saturated (both reserved namespaces:
            # /minio/health/* is the reference-compatible probe alias)
            throttled = not urllib.parse.urlsplit(self.path).path \
                .startswith(("/minio-tpu/", "/minio/"))
            # capture the pool object: admin SetConfigKV can swap
            # srv._req_sem mid-flight, and acquire/release must pair on
            # the same semaphore
            sem = srv._req_sem if throttled else None
            if sem is not None:
                with _stages.stage("admission"):
                    admitted = self._admit(sem)
            else:
                admitted = True
            if not admitted:
                retry_after = max(1, int(srv.requests_deadline_s))
                try:
                    api = s3err.get("SlowDown")
                    self._send(api.http_status,
                               s3err.to_xml(api, self.path,
                                            self._req_id),
                               headers={"Retry-After": str(retry_after)})
                finally:
                    self.close_connection = True
                    try:    # 503s must show up in trace/audit streams
                        self._record_request()
                    except Exception:  # noqa: BLE001 — the 503 itself
                        pass           # must still reach the client
                    _trace.set_request_id("")
                    _trace.set_span_parent("")
                    _stages.clear()
                return
            # slow-body watchdog: absolute per-request budget for
            # reading the body (size-scaled), armed for everything
            # _dispatch_inner pulls off the wire
            try:
                cl = int(self.headers.get("Content-Length") or 0)
            except ValueError:
                cl = 0
            self.rfile.arm(srv.body_budget_s(cl))
            try:
                try:
                    self._dispatch_inner()
                except (ConnectionError, TimeoutError) as e:
                    # the client died mid-body (reset, stalled socket)
                    # or mid-response: the completion record must still
                    # carry the stage vector and an ``aborted`` marker
                    # instead of settling through the generic close
                    # path with no trace of why (tests/
                    # test_chaos_network.py reset drill).  499 is the
                    # client-closed-request convention when no status
                    # ever went out.
                    self._abort_err = f"aborted: {type(e).__name__}"
                    if not self._resp_status:
                        self._resp_status = 499
                    self.close_connection = True
            finally:
                self.rfile.disarm()
                if sem is not None:
                    sem.release()
                try:
                    self._record_request()
                except Exception:   # noqa: BLE001 — never fail a request
                    pass            # on account of observability
                # keep-alive reuses this thread for the next request —
                # its spans must not inherit this request's ID (nor
                # its stage clock, nor its span parent)
                _trace.set_request_id("")
                _trace.set_span_parent("")
                _stages.clear()

        def _admit(self, sem) -> bool:
            """Request-pool admission: wait up to the deadline for a
            slot, but only while the waiting line is short — a full
            queue sheds IMMEDIATELY (503 + Retry-After) instead of
            parking yet another thread (requestsPool deadline,
            cmd/handler-api.go:29-40)."""
            with srv._req_waiters_mu:
                if srv._req_waiters >= srv.requests_queue_max:
                    return False
                srv._req_waiters += 1
            try:
                return sem.acquire(timeout=srv.requests_deadline_s)
            finally:
                with srv._req_waiters_mu:
                    srv._req_waiters -= 1

        def _record_request(self):
            from ..obs import trace as _trace
            dur = _trace.now_ns() - self._t0_ns
            dur_mono = time.monotonic_ns() - self._t0m_ns
            path, bucket, key, query = self._split()
            q1 = {k: v[0] for k, v in query.items()}
            api_name = _api_name(self.command, bucket, key, q1)
            # X-ray completion: close the stage clock against the
            # monotonic request total so the serial vector + ``other``
            # reconciles with it exactly
            clock = _stages.current()
            if clock is not None:
                stage_ns, async_ns, _unattr = clock.finish(dur_mono)
                gating = tuple(clock.gatings)
            else:
                stage_ns, async_ns = {}, {}
                gating = ()
            abort_err = getattr(self, "_abort_err", "")
            srv.flightrec.record(
                self._req_id, api_name, self._resp_status, dur_mono,
                self._rx_bytes, self._resp_bytes,
                stages=tuple(stage_ns.items()),
                async_stages=tuple(async_ns.items()),
                error=abort_err, gating=gating)
            # causal-tree root: the request itself, span id == request
            # id, so every child this request minted (drive ops, rpc
            # legs, quorum gatings — here and on peers) assembles under
            # one root at trace-tree query time.  A compact ring tuple,
            # not a span dict — the idle contract holds.
            _trace.ring_append(self._req_id, self._req_id, "", "http",
                               api_name, self._t0_ns, dur, abort_err,
                               extra=self._resp_status)
            if srv.forensic is not None:
                # Retry-After marks deliberate backpressure (admission
                # or governor sheds) — bounded self-protection, not the
                # breach shape the error-ceiling trigger watches
                srv.forensic.observe_request(
                    self._resp_status,
                    backpressure="Retry-After" in self._resp_headers)
            # metrics-v2 per-API families (cmd/metrics-v2.go
            # getS3RequestsTotalMD / getS3TTFBMetric): request count by
            # api name and the TTFB distribution.  S3 APIs only — the
            # reference scopes these to the S3 router, so health-probe
            # polling and metrics scrapes (reserved /minio-tpu/ and
            # /minio/ namespaces) must not dominate the per-API
            # families; they still ride trace/audit below.
            if not path.startswith(("/minio-tpu/", "/minio/")):
                from ..admin.metrics import GLOBAL as _mtr
                _mtr.inc("mt_s3_requests_api_total", {"api": api_name})
                if self._resp_status >= 400:
                    _mtr.inc("mt_s3_requests_errors_total",
                             {"api": api_name,
                              "status": str(self._resp_status)})
                ttfb = (self._ttfb_ns or dur) / 1e9
                _mtr.observe("mt_s3_ttfb_seconds", {"api": api_name}, ttfb)
                # per-stage latency attribution (the X-ray histogram
                # family): S3 APIs only, same scoping as the per-API
                # counters — ~a dozen stages per API, bounded by the
                # STAGE_NAMES catalog.  ``vec`` keeps the two vectors
                # apart: the serial stages of one API (``other``
                # included) add up to its request wall; async detail
                # overlaps it.  A selector without ``vec`` still sums
                # both, which is what a stage read before the label.
                for vec, vector in (("serial", stage_ns),
                                    ("async", async_ns)):
                    for sname, sns in vector.items():
                        _mtr.observe("mt_s3_stage_seconds",
                                     {"api": api_name, "stage": sname,
                                      "vec": vec}, sns / 1e9)
                # last-minute per-API window (mt_s3_api_last_minute_*
                # gauges + admin `top`): S3 APIs only, same scoping as
                # the per-API counter families above; monotonic delta,
                # unlike the wall-clock trace timestamps
                srv.api_stats.record(api_name, dur_mono,
                                     self._rx_bytes + self._resp_bytes)
                # workload attribution (obs/metering.py): same S3-only
                # scoping as the per-API families; the registry bounds
                # label cardinality internally (sketch-gated tenant
                # rows, capped bucket table, keys never become labels)
                if getattr(srv, "metering", None) is not None:
                    srv.metering.charge(
                        bucket=bucket, api=api_name,
                        tenant=getattr(self, "access_key", ""),
                        key=key, status=self._resp_status,
                        rx=self._rx_bytes, tx=self._resp_bytes,
                        dur_ns=dur_mono)
            if srv.trace_hub.active:
                srv.trace_hub.publish(_trace.make_trace(
                    srv.node_name, api_name,
                    method=self.command, path=path,
                    raw_query="&".join(f"{k}={v}" for k, v in q1.items()),
                    client=self.client_address[0],
                    req_headers=dict(self.headers.items()),
                    status_code=self._resp_status,
                    resp_headers=self._resp_headers,
                    input_bytes=self._rx_bytes,
                    output_bytes=self._resp_bytes,
                    start_ns=self._t0_ns, ttfb_ns=self._ttfb_ns,
                    duration_ns=dur, request_id=self._req_id,
                    detail={"stages": stage_ns,
                            "asyncStages": async_ns,
                            "totalNs": dur_mono} if stage_ns else None))
            if srv.audit.enabled:
                srv.audit.publish(srv.audit.entry(
                    api_name=api_name, bucket=bucket, obj=key,
                    status_code=self._resp_status, rx=self._rx_bytes,
                    tx=self._resp_bytes, duration_ns=dur,
                    remote_host=self.client_address[0],
                    request_id=self._req_id,
                    user_agent=self.headers.get("User-Agent", ""),
                    access_key=getattr(self, "access_key", ""),
                    query=q1,
                    req_headers=dict(self.headers.items()),
                    resp_headers=self._resp_headers))

        def _dispatch_inner(self):
            path, bucket, key, query = self._split()
            from ..admin import handlers as admin_handlers
            from ..admin.metrics import GLOBAL as mtr
            try:
                # SSE-C requires TLS, exactly like AWS (the reference's
                # ErrInsecureSSECustomerRequest gate): a client key in
                # the headers of a plaintext request is already leaked
                # — reject before auth, before anything touches it
                from ..crypto import sse as _csse
                if srv.tls is None and (
                        _csse.SSEC_ALGO in self.headers or
                        _csse.SSEC_COPY_ALGO in self.headers):
                    raise S3Error("InsecureSSECustomerRequest")
                if path.startswith(("/minio-tpu/health/",
                                    "/minio/health/")):
                    # healthcheck router (cmd/healthcheck-router.go:40):
                    # unauthenticated, throttle-exempt — k8s probes must
                    # reach it when the server is saturated or keyless.
                    # "/minio/health/*" is the reference's well-known
                    # probe path — existing deployment manifests keep
                    # working unchanged.
                    self._body()
                    return self._health_api(path, query)
                if path == admin_handlers.METRICS_PATH:
                    self._body()  # drain keep-alive body before replying
                    if self.command != "GET":
                        raise S3Error("MethodNotAllowed")
                    return admin_handlers.handle(self, srv, path, query, b"")
                from . import web as web_handlers
                if path == web_handlers.WEBRPC_PATH or \
                        path == web_handlers.ZIP_PATH or \
                        path.startswith((web_handlers.BROWSER_PATH,
                                         web_handlers.UPLOAD_PREFIX,
                                         web_handlers.DOWNLOAD_PREFIX)):
                    # web endpoints authenticate with their own JWT
                    if web_handlers.handle(self, srv, path, query,
                                           self._body):
                        return
                # browser redirect (cmd/generic-handlers.go
                # setBrowserRedirectHandler): an unauthenticated GET /
                # from a web browser lands on the UI, S3 clients (signed
                # or anonymous API calls) are never redirected
                if path == "/" and self.command == "GET" and \
                        "Mozilla" in self.headers.get("User-Agent", "") \
                        and "Authorization" not in self.headers and \
                        "X-Amz-Credential" not in (query or {}) and \
                        "AWSAccessKeyId" not in (query or {}):
                    self._body()
                    self.send_response(303)
                    self.send_header("Location", web_handlers.BROWSER_PATH)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                if self._try_stream_put(path, bucket, key, query):
                    return
                payload = self._body()
                self._rx_bytes = len(payload)
                mtr.inc("mt_s3_rx_bytes_total", value=len(payload))
                payload = self._auth(path, query, payload)
                if path.startswith("/minio-tpu/"):
                    if admin_handlers.handle(self, srv, path, query,
                                             payload):
                        return
                if bucket in ("minio-tpu", "minio"):
                    # reserved namespaces (isMinioReservedBucket,
                    # cmd/generic-handlers.go): admin/metrics own
                    # "minio-tpu"; "minio" is reserved exactly like the
                    # reference reserves it, so the unauthenticated
                    # /minio/health/* probe router can never shadow a
                    # real bucket's objects
                    raise S3Error("AccessDenied")
                if not bucket:
                    if self.command == "POST":
                        return self._sts_api(payload)
                    return self._list_buckets()
                if not _BUCKET_RE.match(bucket):
                    raise S3Error("InvalidBucketName")
                try:
                    if key:
                        return self._object_api(bucket, key, query,
                                                payload)
                    return self._bucket_api(bucket, query, payload)
                except ol.BucketNotFound:
                    # federated bucket homed on another cluster: 307 to
                    # its owner (cmd/handler-utils.go redirect path)
                    if srv.federation is not None:
                        rec = srv.federation.lookup_other(bucket)
                        if rec is not None:
                            u = urllib.parse.urlsplit(self.path)
                            loc = (f"http://{rec.host}:{rec.port}"
                                   f"{u.path}"
                                   + (f"?{u.query}" if u.query else ""))
                            return self._send(
                                307, b"", headers={"Location": loc})
                    raise
            except ConnectionError:
                # connection death (client reset mid-body or
                # mid-response): there is nobody to send XML to —
                # propagate to the dispatch abort catch, which stamps
                # the flight-recorder row ``aborted: <Exc>``.  A bare
                # TimeoutError is NOT a death: the stalled-socket
                # watchdog fires on a slow-but-alive client, whose
                # socket still deserves the 408 XML below.
                raise
            except Exception as e:  # noqa: BLE001 — every error becomes XML
                self._fail(e, path)

        def _handle(self):
            """Active-request bookkeeping around _dispatch: the graceful
            drain in stop() waits for connections in this window (and
            only these) before severing; once the server is stopping, a
            finishing request closes its connection instead of parking
            for another keep-alive round."""
            with srv._conns_mu:
                srv._active_conns.add(self.connection)
            try:
                self._dispatch()
            finally:
                with srv._conns_mu:
                    srv._active_conns.discard(self.connection)
                if getattr(srv, "_stopping", False):
                    self.close_connection = True

        # PATCH/OPTIONS etc. flow through the same dispatcher and come
        # back as the S3 MethodNotAllowed XML error — the stdlib's raw
        # 501 would leak a non-S3 error shape to clients
        do_GET = do_PUT = do_HEAD = do_DELETE = do_POST = do_PATCH = \
            do_OPTIONS = lambda self: self._handle()

        # -- STS (cmd/sts-handlers.go) -------------------------------------

        STS_NS = "https://sts.amazonaws.com/doc/2011-06-15/"

        def _sts_fail(self, code: str, msg: str = ""):
            root = ET.Element("ErrorResponse", xmlns=self.STS_NS)
            err = ET.SubElement(root, "Error")
            ET.SubElement(err, "Type").text = "Sender"
            ET.SubElement(err, "Code").text = code
            ET.SubElement(err, "Message").text = msg or code
            status = 403 if code in ("AccessDenied", "ExpiredToken") \
                else 400
            self._send(status, _xml(root))

        def _sts_api(self, payload: bytes):
            from ..iam import sts as _sts
            form = {k: v[0] for k, v in urllib.parse.parse_qs(
                payload.decode("utf-8", "replace"),
                keep_blank_values=True).items()}
            action = form.get("Action", "")
            if action in ("AssumeRoleWithWebIdentity",
                          "AssumeRoleWithClientGrants"):
                return self._sts_web_identity(form, action)
            if action == "AssumeRoleWithLDAPIdentity":
                return self._sts_ldap_identity(form)
            if action != "AssumeRole":
                return self._sts_fail("InvalidAction", action)
            if not self.access_key:
                return self._sts_fail("AccessDenied",
                                      "request must be signed")
            try:
                duration = int(form.get("DurationSeconds",
                                        str(_sts.DEFAULT_DURATION_S)))
            except ValueError:
                return self._sts_fail("InvalidParameterValue",
                                      "DurationSeconds")
            policy = form.get("Policy") or None
            try:
                creds = srv.iam.assume_role(self.access_key, duration,
                                            policy)
            except _sts.STSError as e:
                return self._sts_fail(e.code, str(e))
            root = ET.Element("AssumeRoleResponse", xmlns=self.STS_NS)
            result = ET.SubElement(root, "AssumeRoleResult")
            ce = ET.SubElement(result, "Credentials")
            ET.SubElement(ce, "AccessKeyId").text = creds.access_key
            ET.SubElement(ce, "SecretAccessKey").text = creds.secret_key
            ET.SubElement(ce, "SessionToken").text = creds.session_token
            ET.SubElement(ce, "Expiration").text = \
                datetime.datetime.fromtimestamp(
                    creds.expiration, datetime.timezone.utc).strftime(
                        "%Y-%m-%dT%H:%M:%SZ")
            meta = ET.SubElement(root, "ResponseMetadata")
            ET.SubElement(meta, "RequestId").text = uuid.uuid4().hex[:16]
            self._send(200, _xml(root))

        def _sts_ldap_identity(self, form: dict):
            """AssumeRoleWithLDAPIdentity (cmd/sts-handlers.go:436):
            verify the username/password against the configured
            directory, mint temp creds carrying the LDAP-mapped
            policies.  Unsigned by design — the password is the
            credential."""
            from ..iam import ldap as _ldap
            from ..iam import sts as _sts
            if srv.ldap is None or not srv.ldap.config.enabled:
                return self._sts_fail(
                    "NotImplemented",
                    "no LDAP provider configured (identity_ldap)")
            username = form.get("LDAPUsername", "")
            password = form.get("LDAPPassword", "")
            if not username or not password:
                return self._sts_fail(
                    "MissingParameter",
                    "LDAPUsername and LDAPPassword cannot be empty")
            policy = form.get("Policy") or None
            if policy and len(policy) > 2048:
                return self._sts_fail(
                    "InvalidParameterValue",
                    "session policy exceeds 2048 characters")
            try:
                duration = int(form.get(
                    "DurationSeconds", str(srv.ldap.config.sts_expiry_s)))
            except ValueError:
                return self._sts_fail("InvalidParameterValue",
                                      "DurationSeconds")
            try:
                user_dn, groups = srv.ldap.bind(username, password)
            except _ldap.LDAPError as e:
                return self._sts_fail("InvalidParameterValue",
                                      f"LDAP server error: {e}")
            try:
                creds = srv.iam.assume_role_ldap_identity(
                    user_dn, username, groups, duration,
                    session_policy=policy)
            except _sts.STSError as e:
                return self._sts_fail(e.code, str(e))
            except Exception as e:  # noqa: BLE001 — surface as STS error
                return self._sts_fail("InvalidParameterValue", str(e))
            root = ET.Element("AssumeRoleWithLDAPIdentityResponse",
                              xmlns=self.STS_NS)
            result = ET.SubElement(
                root, "AssumeRoleWithLDAPIdentityResult")
            ce = ET.SubElement(result, "Credentials")
            ET.SubElement(ce, "AccessKeyId").text = creds.access_key
            ET.SubElement(ce, "SecretAccessKey").text = creds.secret_key
            ET.SubElement(ce, "SessionToken").text = creds.session_token
            ET.SubElement(ce, "Expiration").text = \
                datetime.datetime.fromtimestamp(
                    creds.expiration, datetime.timezone.utc).strftime(
                        "%Y-%m-%dT%H:%M:%SZ")
            meta = ET.SubElement(root, "ResponseMetadata")
            ET.SubElement(meta, "RequestId").text = uuid.uuid4().hex[:16]
            self._send(200, _xml(root))

        def _sts_web_identity(self, form: dict, action: str):
            """AssumeRoleWithWebIdentity (cmd/sts-handlers.go): validate
            the provider-issued JWT, map the policy claim, mint creds.
            Unsigned by design — the JWT is the credential."""
            from ..iam import openid as _oidc
            from ..iam import sts as _sts
            if srv.openid is None:
                return self._sts_fail(
                    "NotImplemented",
                    "no OpenID provider configured (identity_openid)")
            token = form.get("WebIdentityToken") or form.get("Token", "")
            if not token:
                return self._sts_fail("InvalidParameterValue",
                                      "WebIdentityToken required")
            try:
                duration = int(form.get("DurationSeconds",
                                        str(_sts.DEFAULT_DURATION_S)))
            except ValueError:
                return self._sts_fail("InvalidParameterValue",
                                      "DurationSeconds")
            try:
                claims = srv.openid.authenticate(token)
            except _oidc.OpenIDError as e:
                return self._sts_fail("AccessDenied", str(e))
            policies = srv.openid.policies_of(claims)
            if not policies:
                return self._sts_fail(
                    "AccessDenied",
                    f"token carries no {srv.openid.claim_name!r} claim")
            from ..iam.sys import NoSuchPolicy
            try:
                creds = srv.iam.assume_role_web_identity(
                    claims["sub"], policies, duration)
            except NoSuchPolicy as e:
                return self._sts_fail("AccessDenied",
                                      f"unknown policy: {e}")
            except _sts.STSError as e:
                return self._sts_fail(e.code, str(e))
            root = ET.Element(f"{action}Response", xmlns=self.STS_NS)
            result = ET.SubElement(root, f"{action}Result")
            ce = ET.SubElement(result, "Credentials")
            ET.SubElement(ce, "AccessKeyId").text = creds.access_key
            ET.SubElement(ce, "SecretAccessKey").text = creds.secret_key
            ET.SubElement(ce, "SessionToken").text = creds.session_token
            ET.SubElement(ce, "Expiration").text = \
                datetime.datetime.fromtimestamp(
                    creds.expiration, datetime.timezone.utc).strftime(
                        "%Y-%m-%dT%H:%M:%SZ")
            ET.SubElement(result, "SubjectFromWebIdentityToken").text = \
                claims["sub"]
            meta = ET.SubElement(root, "ResponseMetadata")
            ET.SubElement(meta, "RequestId").text = uuid.uuid4().hex[:16]
            self._send(200, _xml(root))

        def _check_session_token(self):
            """Temp credentials must present their session token on every
            request (checkClaimsFromToken, cmd/auth-handler.go)."""
            from ..iam import sts as _sts
            if not self.access_key:
                return
            try:
                u = srv.iam.get_user(self.access_key)
            except Exception:  # noqa: BLE001 — root or unknown: no claims
                return
            if not (u.parent_user and u.expiration):
                return
            tok = self.headers.get("x-amz-security-token", "") or \
                self._query_token
            if not tok:
                raise S3Error("AccessDenied")
            try:
                claims = _sts.verify_token(tok, srv.iam.root.secret_key)
            except _sts.STSError as e:
                raise S3Error("ExpiredToken" if e.code == "ExpiredToken"
                              else "AccessDenied") from e
            if claims.get("accessKey") != self.access_key:
                raise S3Error("AccessDenied")

        # -- healthcheck router (cmd/healthcheck-router.go:40) ------------

        def _health_api(self, path, query):
            if self.command not in ("GET", "HEAD"):
                raise S3Error("MethodNotAllowed")
            leaf = path.split("/health/", 1)[1]
            status = 200
            headers = {}
            if leaf == "cluster":
                # readiness for traffic incl. maintenance pre-check
                # (cmd/healthcheck-handler.go:28-66 ClusterCheckHandler)
                maint = (query or {}).get("maintenance",
                                          [""])[0] == "true"
                h = srv.layer.health(maintenance=maint)
                if h["write_quorum"]:
                    headers["X-Minio-Write-Quorum"] = \
                        str(h["write_quorum"])
                if not h["healthy"]:
                    if h["healing_drives"]:
                        headers["X-Minio-Healing-Drives"] = \
                            str(h["healing_drives"])
                    # maintenance probe: 412 tells the orchestrator the
                    # node can NOT be safely taken down
                    status = 412 if maint else 503
            elif leaf in ("live", "ready"):
                # process-level probes: always 200 while the process
                # serves, exactly like the reference
                # (cmd/healthcheck-handler.go:69-84 returns success
                # unconditionally); a stopping server only annotates
                # the informational offline header
                if getattr(srv, "_stopping", False):
                    headers["X-Minio-Server-Status"] = "offline"
            else:
                raise S3Error("NoSuchKey")
            self._send(status, b"", headers=headers)

        # -- service / bucket APIs ----------------------------------------

        # bucket/object handler families live in handlers_bucket.py /
        # handlers_object.py (split from this file, attached below)

    # handler-family modules (split from this file): plain functions
    # taking the handler instance; srv rides on the class
    from . import handlers_bucket, handlers_object
    Handler.srv = srv
    Handler.TAG_KEY = handlers_object.TAG_KEY
    for _mod in (handlers_bucket, handlers_object):
        for _name in _mod.HANDLERS:
            setattr(Handler, _name, getattr(_mod, _name))

    return Handler


def _actual_size(oi) -> int:
    """Client-visible size (GetActualSize, cmd/object-api-utils.go): the
    pre-compression size for compressed objects, the DARE-plaintext size
    for encrypted-only objects, else the stored size."""
    from ..crypto import sse as csse
    raw = oi.user_defined.get(csse.META_ACTUAL_SIZE)
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    if csse.is_encrypted(oi.user_defined):
        try:
            return csse.decrypted_size(oi.user_defined, oi.size, oi.parts)
        except Exception:  # noqa: BLE001 — corrupt meta: report stored size
            pass
    return oi.size


def _parse_range(spec: str) -> tuple[int, int]:
    """HTTP Range -> (offset, length) without knowing the size
    (cmd/httprange.go); negative offset = suffix, length -1 = to-end.
    Size-dependent validation/clamping happens in the object layer, so a
    ranged GET costs a single quorum metadata read."""
    m = re.match(r"^bytes=(\d*)-(\d*)$", spec.strip())
    if not m:
        raise S3Error("InvalidRange")
    first, last = m.group(1), m.group(2)
    if first == "" and last == "":
        raise S3Error("InvalidRange")
    if first == "":  # suffix range: last N bytes
        n = int(last)
        if n == 0:
            raise S3Error("InvalidRange")
        return -n, -1
    start = int(first)
    if last == "":
        return start, -1
    end = int(last)
    if end < start:
        raise S3Error("InvalidRange")
    return start, end - start + 1
