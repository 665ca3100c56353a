"""Drive lifecycle state machine.

Reference behavior being matched:
  * cmd/erasure-sets.go:196-332 — connectDisks + monitorAndConnectEndpoints:
    a background monitor reconnects offline drives and verifies their
    format/identity before re-admitting them;
  * cmd/xl-storage-disk-id-check.go — per-drive wrapper validating disk
    identity so a swapped drive is never written as if it were the old one;
  * cmd/background-newdisks-heal-ops.go:44,113 — a drive that returns
    fresh/wiped is reformatted with its expected identity and the set is
    healed onto it;
  * cmd/storage-rest-client.go:651-662 — health-checked remote clients
    fail fast while offline instead of hammering a dead peer.

``HealthDisk`` wraps any StorageAPI (local XLStorage or RemoteStorage)
with a circuit breaker: data calls on an offline drive raise DiskNotFound
immediately; after a cooldown one call is allowed through as a half-open
probe.  ``DriveMonitor`` is the background reconnect loop: it probes
offline drives, re-admits healthy ones (rewriting format.json on wiped
drives), revalidates identity of online drives, and fires the heal
callback for every returned drive.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from . import errors
from .format import FORMAT_FILE, FormatErasure
from .xl_storage import SYS_DIR, XLStorage
from ..utils.locktrace import mtlock

# data-plane methods gated by the circuit breaker; identity/health
# accessors pass straight through
_GUARDED = {
    "make_vol", "list_vols", "stat_vol", "delete_vol", "list_dir",
    "read_all", "write_all", "create_file", "append_file",
    "read_file_stream", "rename_file", "delete", "stat_info_file",
    "rename_data", "write_data_commit", "write_metadata",
    "update_metadata", "read_version",
    "list_versions", "delete_version", "verify_file", "check_parts",
    "walk_dir", "walk_entries", "tmp_dir", "clean_tmp", "disk_info",
}


def slow_drive_knobs(config=None) -> tuple[float, int]:
    """(multiple, min_samples) from the ``drive`` kvconfig subsystem —
    resolved per call, so admin SetConfigKV retunes detection live.
    With no Config handed in, a fresh one still honors env overrides
    (MT_DRIVE_SLOW_LATENCY_MULTIPLE / MT_DRIVE_SLOW_MIN_SAMPLES)."""
    if config is None:
        from ..utils.kvconfig import Config
        config = Config()
    try:
        multiple = float(config.get("drive", "slow_latency_multiple"))
    except (KeyError, ValueError):
        multiple = 4.0
    try:
        min_samples = int(config.get("drive", "slow_min_samples"))
    except (KeyError, ValueError):
        min_samples = 10
    return max(multiple, 1.0), max(min_samples, 1)


def slow_drives(disks, multiple: float = 4.0, min_samples: int = 10
                ) -> dict[str, dict]:
    """Slow-drive detection over ONE erasure set's last-minute latency
    windows, op by op: each op a drive ran is compared with the median
    p50 of the same op on the OTHER drives of the set, and a drive whose
    typical op (the sample-weighted median of those ratios) is over
    ``multiple`` is flagged (tail-at-scale hedging signal, Dean &
    Barroso 2013) — flagged in health/metrics output, never ejected;
    ejection stays the circuit breaker's job and needs hard failures,
    not latency.

    Op by op because a drive's mix is its own: a data drive serves shard
    reads a parity drive does not, and a quorum metadata read that the
    native wave makes (xl_storage.read_version_wave) costs tens of us
    where a write costs ms; one median over all of a drive's ops sits
    wherever its mix puts it.

    Leave-one-out median: comparing a drive against a median that
    includes itself lets a single outlier in a small set DRAG the
    median up to its own p50 and never trip (2 drives: median == the
    slow drive).  Callers with a multi-set layer group per set first
    (slow_drives_for_layer) so an HDD pool never masks a failing NVMe.

    Returns {endpoint: {"p50_ns", "samples", "median_ns", "slow"}} for
    drives with any last-minute traffic: ``p50_ns`` the drive's median
    over all its ops, ``median_ns`` what its peers' pace gives for the
    same mix (``p50_ns`` over the typical ratio; 0 with no op in
    common)."""
    from ..obs.lastminute import drive_windows
    wins = drive_windows(disks)
    stats, ops = {}, {}
    for endpoint, w in wins.items():
        samples = sum(c for c, _, _ in w.totals().values())
        if not samples:
            continue
        stats[endpoint] = {"p50_ns": w.p50_all(), "samples": samples}
        mine = ops[endpoint] = {}       # op -> (p50, live samples)
        for op, win in list(w.windows.items()):
            live = sorted(win.live_samples())
            if live:
                mine[op] = (live[len(live) // 2], len(live))
    for endpoint, v in stats.items():
        ratios = []
        for op, (p50, n) in ops[endpoint].items():
            others = sorted(o[op][0] for e, o in ops.items()
                            if e != endpoint and op in o)
            median = others[len(others) // 2] if others else 0
            if median > 0:
                ratios.append((p50 / median, n))
        ratio = _weighted_median(ratios)
        v["median_ns"] = int(v["p50_ns"] / ratio) if ratio else 0
        v["slow"] = bool(ratio and v["samples"] >= min_samples
                         and ratio > multiple)
    return stats


def _weighted_median(pairs: list[tuple[float, int]]) -> float:
    """The median of ``(value, weight)`` pairs, each value counted
    ``weight`` times (the upper one of an even count, as a window's p50
    takes it); 0 for none."""
    half = sum(w for _, w in pairs) // 2
    seen = 0
    for value, w in sorted(pairs):
        seen += w
        if seen > half:
            return value
    return 0.0


def disks_by_set(layer) -> list[list]:
    """Per-erasure-set drive lists for every topology shape (flat /
    sets / pools-of-sets) — the storage layer's own traversal, shared
    with the admin scrape so neither depends on the other's internals."""
    if hasattr(layer, "pools"):
        return [list(s.disks) for p in layer.pools for s in p.sets]
    if hasattr(layer, "sets"):
        return [list(s.disks) for s in layer.sets]
    disks = getattr(layer, "disks", None)   # FS/gateway layers: none
    return [list(disks)] if disks else []


def slow_drives_for_layer(layer, multiple: float = 4.0,
                          min_samples: int = 10) -> dict[str, dict]:
    """slow_drives() grouped PER ERASURE SET across any topology shape
    — the detection contract compares a drive against its set peers
    (same workload, same shard fan-out), never against other pools."""
    out: dict[str, dict] = {}
    for dlist in disks_by_set(layer):
        out.update(slow_drives(dlist, multiple=multiple,
                               min_samples=min_samples))
    return out


class HealthDisk:
    """Circuit-breaking StorageAPI proxy with identity verification."""

    def __init__(self, inner, expected_format: Optional[FormatErasure] = None,
                 cooldown_s: float = 2.0,
                 on_return: Optional[Callable[["HealthDisk", str], None]]
                 = None):
        self.inner = inner
        self.expected_format = expected_format
        self.cooldown_s = cooldown_s
        self.on_return = on_return
        self._offline = False
        self._offline_since = 0.0
        self._next_probe = 0.0
        self._mu = mtlock("drive.health")

    # -- state -------------------------------------------------------------

    def is_online(self) -> bool:
        return not self._offline and self.inner.is_online()

    @property
    def offline(self) -> bool:
        return self._offline

    def endpoint(self) -> str:
        return self.inner.endpoint()

    def is_local(self) -> bool:
        return self.inner.is_local()

    def get_disk_id(self) -> str:
        return self.inner.get_disk_id()

    def set_disk_id(self, disk_id: str) -> None:
        self.inner.set_disk_id(disk_id)

    def close(self) -> None:
        self.inner.close()

    def _mark_offline(self) -> None:
        with self._mu:
            if not self._offline:
                self._offline = True
                self._offline_since = time.monotonic()
            self._next_probe = time.monotonic() + self.cooldown_s

    def _mark_online(self, how: str) -> None:
        fire = False
        with self._mu:
            if self._offline:
                self._offline = False
                fire = True
        if fire and self.on_return is not None:
            # heal kick must not block the call path
            threading.Thread(target=self.on_return, args=(self, how),
                             daemon=True,
                             name="mt-drive-heal-kick").start()

    # -- probe / reconnect (connectDisks, cmd/erasure-sets.go:196) ---------

    def probe(self) -> str | None:
        """Try to (re)admit the drive.  Returns how it came back
        ('reconnected' | 'reformatted') or None if still unhealthy.
        Identity rules: format.json must carry the expected disk UUID; a
        wiped drive (no format.json) is reformatted with its expected
        identity (background-newdisks-heal-ops analog); a FOREIGN format
        (different deployment/drive id — a swapped drive) stays offline."""
        try:
            if not self.inner.is_online():
                self._mark_offline()
                return None
            try:
                raw = self.inner.read_all(SYS_DIR, FORMAT_FILE)
                fmt = FormatErasure.from_json(raw)
            except (errors.FileNotFound, errors.VolumeNotFound):
                fmt = None
            if fmt is None:
                if self.expected_format is None:
                    # formatless deployments (tests, raw dirs): admit
                    self._mark_online("reconnected")
                    return "reconnected"
                # wiped/replaced drive: stamp its expected identity, then
                # the heal callback repopulates it
                try:
                    self.inner.make_vol(SYS_DIR)
                except errors.VolumeExists:
                    pass
                self.inner.write_all(
                    SYS_DIR, FORMAT_FILE,
                    self.expected_format.to_json().encode())
                self.inner.set_disk_id(self.expected_format.this)
                self._mark_online("reformatted")
                return "reformatted"
            if self.expected_format is not None and (
                    fmt.id != self.expected_format.id
                    or fmt.this != self.expected_format.this):
                # swapped drive: NEVER write to it as if it were ours
                self._mark_offline()
                return None
            self.inner.set_disk_id(fmt.this)
            self._mark_online("reconnected")
            return "reconnected"
        except Exception:  # noqa: BLE001 — still down
            self._mark_offline()
            return None

    # -- guarded call path -------------------------------------------------

    def _guard(self, fn, *args, **kwargs):
        if self._offline:
            if time.monotonic() < self._next_probe:
                raise errors.DiskNotFound(
                    f"{self.endpoint()}: drive offline")
            # half-open: one probe attempt per cooldown window
            if self.probe() is None:
                raise errors.DiskNotFound(
                    f"{self.endpoint()}: drive offline")
        try:
            return fn(*args, **kwargs)
        except Exception:
            # benign per-file errors must not trip the breaker; only an
            # unhealthy drive (root gone, transport down) goes offline
            try:
                healthy = self.inner.is_online()
            except Exception:  # noqa: BLE001
                healthy = False
            if not healthy:
                self._mark_offline()
            raise

    def guarded(self, fn, *args):
        """``fn(*args)`` as a call of this drive, under :meth:`_guard`'s
        rules: the entry a read wave (xl_storage.read_version_wave)
        takes for the part of a drive's read it does in Python, so the
        breaker's rules live in ``_guard`` alone."""
        return self._guard(fn, *args)

    def wave_storage(self) -> XLStorage | None:
        """The local drive under this one when a quorum metadata read may
        read its ``xl.meta`` in the native wave
        (xl_storage.wave_target): a plain ``XLStorage`` while the drive
        is online.  None for an offline drive: it stays a pool child,
        whose call :meth:`_guard` refuses inside its cooldown or lets
        :meth:`probe` decide after, so the wave never reads it."""
        inner = self.inner
        return inner if not self._offline and type(inner) is XLStorage \
            else None

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if name in _GUARDED and callable(attr):
            def guarded(*args, _fn=attr, **kwargs):
                return self._guard(_fn, *args, **kwargs)
            return guarded
        return attr


def wrap_disks(disks: list, fmt: Optional[FormatErasure] = None,
               set_drive_count: int | None = None,
               on_return: Optional[Callable[[HealthDisk, str], None]] = None,
               cooldown_s: float = 2.0) -> list[HealthDisk]:
    """Wrap a flat drive list in HealthDisks, pinning each drive's
    expected identity from the format grid (flat order == grid order,
    cmd/format-erasure.go)."""
    out = []
    for i, d in enumerate(disks):
        expected = None
        if fmt is not None and fmt.sets:
            sdc = set_drive_count or len(fmt.sets[0])
            expected = FormatErasure(
                id=fmt.id, sets=fmt.sets,
                this=fmt.sets[i // sdc][i % sdc],
                distribution_algo=fmt.distribution_algo)
        out.append(HealthDisk(d, expected_format=expected,
                              cooldown_s=cooldown_s, on_return=on_return))
    return out


class DriveMonitor:
    """monitorAndConnectEndpoints (cmd/erasure-sets.go:269): probe
    offline drives every interval; revalidate online drives' identity
    every ``verify_every`` cycles (disk-id check analog)."""

    def __init__(self, disks: list[HealthDisk], interval_s: float = 5.0,
                 verify_every: int = 12):
        self.disks = [d for d in disks if isinstance(d, HealthDisk)]
        self.interval_s = interval_s
        self.verify_every = verify_every
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._cycles = 0

    def poll_once(self) -> None:
        self._cycles += 1
        deep = self.verify_every and self._cycles % self.verify_every == 0
        for d in self.disks:
            try:
                if d.offline:
                    d.probe()
                elif deep:
                    # identity revalidation catches silently swapped
                    # drives (xl-storage-disk-id-check semantics)
                    if not d.inner.is_online():
                        d._mark_offline()
                    elif d.expected_format is not None:
                        try:
                            raw = d.inner.read_all(SYS_DIR, FORMAT_FILE)
                            fmt = FormatErasure.from_json(raw)
                            if fmt.this != d.expected_format.this:
                                d._mark_offline()
                        except (errors.FileNotFound,
                                errors.VolumeNotFound):
                            d._mark_offline()
            except Exception:  # noqa: BLE001 — monitor must survive
                pass

    def start(self) -> None:
        def loop():
            while not self._stop.wait(self.interval_s):
                self.poll_once()
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="mt-drive-health-poll")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)


def wrap_with_heal(disks: list, fmt: Optional[FormatErasure],
                   set_drive_count: int | None
                   ) -> tuple[list[HealthDisk], Callable]:
    """Wrap drives with lifecycle proxies whose heal-on-return targets
    the owning erasure set.  Returns (wrapped_disks, bind_layer); call
    bind_layer(sets_layer) once the ErasureSets object exists — the
    callback resolves the set lazily through it."""
    holder: dict = {}

    def layer_for(hd):
        layer = holder.get("layer")
        return layer.set_for_disk(hd) if layer else None

    wrapped = wrap_disks(disks, fmt, set_drive_count,
                         on_return=heal_on_return(layer_for))

    def bind_layer(layer) -> None:
        holder["layer"] = layer

    return wrapped, bind_layer


def heal_on_return(layer_for) -> Callable[[HealthDisk, str], None]:
    """Standard on_return callback: sweep-heal every set that contains
    the returned drive (monitorLocalDisksAndHeal,
    cmd/background-newdisks-heal-ops.go:113)."""

    def cb(disk: HealthDisk, how: str) -> None:
        try:
            target = layer_for(disk)
            if target is None:
                return
            from ..background.heal import BackgroundHealer
            BackgroundHealer(layer=target).sweep()
        except Exception:  # noqa: BLE001 — heal retried by the sweep
            pass

    return cb
