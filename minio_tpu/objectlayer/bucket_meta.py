"""Per-bucket metadata/config store (cmd/bucket-metadata-sys.go).

The reference persists one msgp blob per bucket under
``.minio.sys/buckets/<bucket>/.metadata.bin`` caching versioning, policy,
lifecycle, replication, ... configs.  Here: a JSON blob written to every
drive's system volume with quorum, cached in memory, holding the config
sub-documents as they land (versioning first; policy/lifecycle/etc. attach
to the same document).

``get`` remembers the answer of its quorum read whether or not there is
a document.  A document stays until ``update`` / ``drop`` /
``invalidate`` (a peer's ``reload_bucket_meta``); the EMPTY answer — a
bucket nobody configured, or a name that does not exist — also ages out
after ``BUCKET_TTL_S``, so a first configuration whose peer reload never
arrived is seen within that TTL, and at most ``_EMPTY_MAX`` of them are
kept, so probes of unknown names cannot grow the cache.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Optional

from ..admin.metrics import GLOBAL as _metrics
from ..storage import errors as serrors
from ..storage.xl_storage import SYS_DIR
from .erasure_object import BUCKET_TTL_S

_now = time.monotonic          # the tests step it

_LOOKUPS = "mt_bucket_meta_lookups_total"
_HIT = {"result": "hit"}
_READ = {"result": "read"}      # a drive fan-out was made


class BucketMetadataSys:
    # empty answers kept at once; ``_allow`` runs before any bucket-
    # existence check, so anonymous probes of random names reach ``get``
    _EMPTY_MAX = 1024

    def __init__(self, er):
        self._er = er            # ErasureObjects (or sets facade)
        self._cache: dict[str, dict] = {}
        # bucket -> when its cached EMPTY answer expires; one TTL for
        # all, so insertion order is expiry order
        self._empty: dict[str, float] = {}
        self._parsed_cache: dict[tuple[str, str], tuple[str, Any]] = {}
        # bumped by every update/drop/invalidate: a read that started
        # before the change must not store what it saw after it
        self._gen = 0
        self._mu = threading.Lock()
        # peer fan-out hook: set by attach_peers so config changes reload
        # on every node before the change is acknowledged
        # (peerRESTMethodLoadBucketMetadata)
        self.on_change = None
        for labels in (_HIT, _READ):    # both in every scrape, from zero
            _metrics.inc(_LOOKUPS, labels, 0.0)

    def invalidate(self, bucket: str) -> None:
        """Drop the in-memory caches for one bucket (peer reload path):
        the next access re-reads the quorum document from the drives."""
        with self._mu:
            self._gen += 1
            self._cache.pop(bucket, None)
            self._empty.pop(bucket, None)
            for key in [k for k in self._parsed_cache if k[0] == bucket]:
                self._parsed_cache.pop(key, None)

    def _path(self, bucket: str) -> str:
        return f"buckets/{bucket}/bucket-meta.json"

    def get(self, bucket: str) -> dict:
        with self._mu:
            doc = self._cache.get(bucket)
            if doc is None and self._empty.get(bucket, 0.0) > _now():
                doc = {}
            gen = self._gen
        if doc is not None:
            _metrics.inc(_LOOKUPS, _HIT)
            return doc
        doc = self._read(bucket)
        with self._mu:
            if gen == self._gen:
                if doc:
                    self._cache[bucket] = doc
                else:
                    self._remember_empty(bucket)
        return doc

    def _remember_empty(self, bucket: str) -> None:
        """Cache "no document" until the TTL; expired entries and, past
        ``_EMPTY_MAX``, the oldest go.  Caller holds ``_mu``."""
        now = _now()
        self._empty.pop(bucket, None)       # re-insert at the young end
        self._empty[bucket] = now + BUCKET_TTL_S
        while True:
            oldest = next(iter(self._empty))
            if len(self._empty) <= self._EMPTY_MAX \
                    and self._empty[oldest] > now:
                return
            del self._empty[oldest]

    def _read(self, bucket: str) -> dict:
        """The quorum document off the drives, ``{}`` where none is."""
        _metrics.inc(_LOOKUPS, _READ)
        res, _ = self._er._fanout(
            lambda d: d.read_all(SYS_DIR, self._path(bucket)))
        # newest revision wins: a drive that missed the last quorum write
        # must not roll the config back (e.g. silently disable versioning)
        doc = {}
        for r in res:
            if r is None:
                continue
            try:
                cand = json.loads(r)
            except json.JSONDecodeError:
                continue
            if cand.get("_rev", 0) >= doc.get("_rev", 0):
                doc = cand
        return doc

    def update(self, bucket: str, key: str, value: Any) -> None:
        # read-modify-write from the drives, never from a cached answer
        # that may predate another node's change
        doc = self._read(bucket)
        if value is None:
            doc.pop(key, None)
        else:
            doc[key] = value
        doc["_rev"] = doc.get("_rev", 0) + 1
        blob = json.dumps(doc).encode()
        _, errs = self._er._fanout(
            lambda d: d.write_all(SYS_DIR, self._path(bucket), blob))
        ok = sum(1 for e in errs if e is None)
        if ok < len(errs) // 2 + 1:
            raise serrors.FaultyDisk(
                f"bucket metadata write reached only {ok} drives")
        with self._mu:
            self._gen += 1
            self._cache[bucket] = doc
            self._empty.pop(bucket, None)
        if self.on_change is not None:
            self.on_change(bucket)

    def drop(self, bucket: str) -> None:
        self._er._fanout(
            lambda d: d.delete(SYS_DIR, f"buckets/{bucket}",
                               recursive=True))
        self.invalidate(bucket)
        if self.on_change is not None:
            self.on_change(bucket)

    # -- typed accessors ---------------------------------------------------

    def get_config(self, bucket: str, name: str) -> Optional[str]:
        """Raw stored config document (XML/JSON string) or None."""
        v = self.get(bucket).get(name)
        if isinstance(v, dict):
            return v.get("raw")
        return v

    def get_parsed(self, bucket: str, name: str, parser):
        """Parsed form of a stored config, cached keyed on the raw
        document — request paths must not re-parse XML/JSON per call."""
        raw = self.get_config(bucket, name)
        if raw is None:
            return None
        key = (bucket, name)
        with self._mu:
            cached = self._parsed_cache.get(key)
            if cached is not None and cached[0] == raw:
                return cached[1]
        parsed = parser(raw.encode())
        with self._mu:
            self._parsed_cache[key] = (raw, parsed)
        return parsed

    def get_bucket_policy(self, bucket: str):
        from ..bucket.policy import BucketPolicy
        return self.get_parsed(bucket, "policy", BucketPolicy.parse)

    def set_config(self, bucket: str, name: str,
                   raw: Optional[str]) -> None:
        self.update(bucket, name, raw)

    def versioning_enabled(self, bucket: str) -> bool:
        return self.get(bucket).get("versioning", {}).get(
            "status") == "Enabled"

    def set_versioning(self, bucket: str, enabled: bool) -> None:
        self.update(bucket, "versioning",
                    {"status": "Enabled" if enabled else "Suspended"})
