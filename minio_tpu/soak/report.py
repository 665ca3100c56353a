"""Scenario runner + the ``BENCH_*``-shaped ``SOAK_r*.json`` report.

One :class:`Scenario` = (workload mix, chaos timeline, duration, SLO
budget).  :func:`run_scenario` boots a fresh proxied cluster, drives
the mix while the conductor replays the timeline, then runs the full
SLO assertion sweep (last-minute p50/p99 per API, error-rate ceiling,
zero telemetry dead-letters, heal convergence, thread hygiene) and
returns one ``{scenario, metric, value, unit, detail, passed}`` row
per assertion.  :func:`run_matrix` sequences scenarios and writes the
matrix report — the ``bench.py soak`` leg.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from dataclasses import dataclass, field

from . import chaos as _chaos
from . import slo as _slo
from .workload import MIXES, Mix, WorkloadGenerator


class SoakStatus:
    """Live status a running conductor attaches to the S3 server
    (read by the admin ``soak-status`` route)."""

    def __init__(self, scenario: str):
        self.scenario = scenario
        self.state = "running"
        self.started_ns = time.time_ns()
        self._mu = threading.Lock()
        self._rows: list[dict] = []

    def finish(self, rows: list[dict]) -> None:
        with self._mu:
            self._rows = rows
            self.state = "done"

    def snapshot(self) -> dict:
        with self._mu:
            rows = list(self._rows)
        return {
            "scenario": self.scenario,
            "state": self.state,
            "startedNs": self.started_ns,
            "assertions": len(rows),
            "failed": sum(1 for r in rows if not r.get("passed")),
        }


@dataclass
class Scenario:
    name: str
    mix: Mix
    timeline: list[_chaos.Event]
    duration_s: float = 12.0
    budget: _slo.Budget = field(default_factory=_slo.Budget)
    workers: int = 2
    nodes: int = 3
    drives_per_node: int = 2
    # codec backend for the cluster's erasure layers: the small-object
    # storm runs "tpu" so its encode/decode dispatches ride the
    # cross-request batcher (the numpy layer's native one-copy framed
    # path never leaves the host)
    backend: str = "numpy"
    # huge_put drill (ISSUE 12 tentpole c): when non-zero, a single
    # object of this many bytes is PUT through the layer mid-chaos
    # (0.3 x duration in — after the drive kill, during the slow-drive
    # window) and read back byte-correct, while the mix keeps storming
    # — one big mesh-sharded transfer must not wreck the small-op SLOs
    huge_put_bytes: int = 0
    # full-TLS cluster (ISSUE 13): an ephemeral PKI is minted into the
    # scenario dir and BOTH planes come up encrypted — S3 front +
    # internode mTLS — with the whole chaos timeline landing on
    # encrypted links (mid-handshake resets, mid-encrypted-frame
    # faults).  Same mix, same SLO budget: TLS must not cost SLO.
    tls: bool = False
    # per-scenario env overrides applied around the run (on top of
    # _SOAK_ENV) — the forensic drill lowers the trigger thresholds
    # through the kvconfig MT_* env layer
    env: dict = field(default_factory=dict)
    # elastic-topology cluster (ISSUE 16): node0's layer is wrapped in
    # ErasureServerPools with a Rebalancer on the background plane, so
    # the timeline can fire ``pool_add`` / ``pool_decommission`` events
    # mid-storm; pair with MT_REBALANCE_ENABLE=on in ``env``
    pools: bool = False
    # SLO watchdog scenario (ISSUE 18): the runner hosts a live HTTP
    # alert sink and wires it as the ``alert_webhook`` egress endpoint
    # before the cluster boots (the sink's port is only known at run
    # time, so it cannot live in the scenario's env literal); pair
    # with MT_WATCHDOG_ENABLE=on in ``env``.  The watchdog verdict
    # (_watchdog_summary) feeds the Budget's alert rows.
    watchdog: bool = False
    # workload attribution scenario (ISSUE 19): extra per-tenant
    # workloads beside the root generator's.  Each entry is
    # (access_key, Mix, workers) — the runner mints the IAM user
    # (readwrite policy), gives it its own bucket, and drives one
    # WorkloadGenerator per tenant concurrently; per-tenant verdicts
    # feed the Budget's noisy-neighbor / quota rows.  When
    # ``quota_bytes`` is set, the FIRST tenant (the noisy one) gets a
    # HARD quota on its bucket through the live admin surface before
    # its workload starts, so its writes bounce mid-storm on the real
    # enforcement path
    tenants: tuple = ()
    quota_bytes: int = 0


# chaos knobs every scenario runs under: snappy breakers so fault
# detection and re-admission fit the scenario window (the same env the
# chaos drills pin), applied around the run and restored after
_SOAK_ENV = {
    "MT_RPC_BREAKER_FAILURES": "2",
    "MT_RPC_BREAKER_COOLDOWN": "200ms",
    "MT_RPC_RETRY_ATTEMPTS": "1",
    "MT_API_SHUTDOWN_DRAIN_S": "5s",
    # memory-governor watermark for the matrix: generous enough that
    # the mixes run, low enough that a leak or an unbounded path would
    # pile charges into visible sheds / a non-zero inuse residue the
    # memory SLO rows catch (soak/slo.py require_mem_bounded)
    "MT_API_MEM_LIMIT": "256MiB",
}


def _chaos_timeline(t: float) -> list[_chaos.Event]:
    """The standard non-overlapping fault sequence scaled to a
    ``t``-second scenario: drive death mid-churn → return, slow drive
    → recover, peer partition → heal, 503 burst → heal.  Faults never
    overlap in a way that loses write quorum (6 drives, parity 2)."""
    E = _chaos.Event
    return [
        E(0.08 * t, "drive_kill", drive=0),
        E(0.28 * t, "drive_return", drive=0),
        E(0.34 * t, "drive_slow", drive=1, delay_s=0.04),
        E(0.52 * t, "drive_fast", drive=1),
        E(0.58 * t, "partition", node=2),
        E(0.74 * t, "heal_link", node=2),
        E(0.80 * t, "burst_503", node=1),
        E(0.90 * t, "heal_link", node=1),
    ]


def default_matrix(duration_s: float = 15.0) -> list[Scenario]:
    """The acceptance matrix: every production mix under the full
    concurrent chaos timeline.  The error budget is 10%: two of the
    timeline's windows hold the set at EXACTLY write quorum, where the
    first write per faulted drive-client must fail before its breaker
    opens — bounded, expected shedding, not an SLO miss.

    The small-object storm runs with doubled workers (it exists to
    overlap tiny encode/decode dispatches) and additionally asserts a
    non-zero ``mt_codec_batch_occupancy`` from the live scrape — the
    batching codec service must actually engage under its target
    load."""
    out = []
    for mix in MIXES.values():
        storm = mix.name == "small_object_storm"
        # the bounded-memory storms (streaming Select over multi-block
        # objects, listing over a wide namespace) run with doubled
        # workers under the governor watermark and assert the memory
        # SLO rows on the live scrape
        membound = mix.name in ("select_storm", "listing_storm")
        # the zipf hot-read storm runs with doubled workers so
        # concurrent GETs of the hot keys actually overlap, and
        # asserts the hot_read_engaged / cache_bytes_accounted /
        # stale_reads rows — mid-storm overwrites ride the mix, so the
        # digest oracle exercises invalidate-before-visible for real
        hot = mix.name == "hot_get_storm"
        out.append(Scenario(
            name=mix.name, mix=mix,
            timeline=_chaos_timeline(duration_s),
            duration_s=duration_s,
            budget=_slo.Budget(max_error_rate=0.10,
                               require_codec_occupancy=storm,
                               # the storm's tiny concurrent PUTs are
                               # the group-commit plane's target load:
                               # assert batches formed, fsyncs were
                               # saved and packed segments absorbed
                               # bytes on the live scrape (ISSUE 20)
                               require_group_commit=storm,
                               require_mem_bounded=membound,
                               require_hot_read=hot,
                               # ordinary chaos is not a breach: the
                               # trigger engine (default thresholds)
                               # must stay quiet through the matrix
                               require_no_forensics=True,
                               # every storm must show quorum gating
                               # attribution on the live scrape — the
                               # critical-path engine rode the storm
                               require_xray=True),
            workers=4 if storm or membound or hot else 2,
            backend="tpu" if storm else "numpy"))
    # huge_put: one mesh-sharded object (1 GiB on a TPU host,
    # MT_SOAK_HUGE_BYTES overrides) PUT mid-chaos on the mesh-backend
    # cluster while the GET-heavy mix storms — the byte-correct
    # round-trip AND the small-op p99s are both assertion rows
    out.append(Scenario(
        name="huge_put", mix=MIXES["get_heavy_small"],
        timeline=_chaos_timeline(duration_s),
        duration_s=duration_s,
        budget=_slo.Budget(max_error_rate=0.10,
                           require_xray=True),
        workers=2, backend="mesh",
        huge_put_bytes=_huge_bytes_default()))
    # forensic_drill (ISSUE 15 acceptance): induced SLO breach —
    # burst_503 on BOTH peer links kills write/read quorum mid-storm
    # while a drive runs slow, the error ceiling crosses, and exactly
    # ONE forensic bundle must land with the breach window's request
    # records inside (cooldown outlasts the scenario); clean scenarios
    # above assert the engine stayed quiet
    out.append(forensic_drill_scenario(duration_s))
    # tls_storm (ISSUE 13 acceptance): the GET-heavy mix under the
    # FULL chaos timeline with S3 + internode both encrypted — the
    # same SLO budget as the plaintext matrix, so any TLS-induced
    # regression fails a row; skipped only where the image has no
    # openssl binary to mint the ephemeral PKI with
    from ..secure import pki as _pki
    if _pki.available():
        out.append(Scenario(
            name="tls_storm", mix=MIXES["get_heavy_small"],
            timeline=_chaos_timeline(duration_s),
            duration_s=duration_s,
            budget=_slo.Budget(max_error_rate=0.10,
                               require_xray=True),
            workers=2, tls=True))
    return out


def _huge_bytes_default() -> int:
    """1 GiB where the mesh actually has chips; a CPU-only harness
    (virtual mesh, interpret-mode kernels) scales the drill down so
    the matrix stays runnable everywhere."""
    env = os.environ.get("MT_SOAK_HUGE_BYTES")
    if env:
        return int(env)
    from ..ops import device
    return 1 << 30 if device.platform() == "tpu" else 32 << 20


def forensic_drill_scenario(duration_s: float = 12.0) -> Scenario:
    """The induced-breach drill (burst_503 + drive_slow, then the
    killing blow): drive 1 runs slow, then BOTH node0-local drives die
    while node1's internode link 503-bursts — reads and writes lose
    drive quorum and fail FAST (the dsync lock keeps its node0+node2
    majority, so requests error instead of parking in lock_wait), a
    genuine majority-5xx breach.  Trigger thresholds are lowered
    through the kvconfig env layer so the error ceiling crosses within
    the breach window; the cooldown outlasts the scenario, so exactly
    one bundle can land."""
    E = _chaos.Event
    t = duration_s
    return Scenario(
        name="forensic_drill", mix=MIXES["get_heavy_small"],
        timeline=[
            E(0.08 * t, "drive_slow", drive=1, delay_s=0.02),
            E(0.20 * t, "drive_kill", drive=0),
            E(0.22 * t, "drive_kill", drive=1),
            E(0.25 * t, "burst_503", node=1),
            E(0.68 * t, "heal_link", node=1),
            E(0.70 * t, "drive_return", drive=0),
            E(0.72 * t, "drive_return", drive=1),
        ],
        duration_s=duration_s,
        # the breach IS the point: no error-rate ceiling, no p99
        # budget small enough to trip on the induced outage
        budget=_slo.Budget(max_error_rate=1.0,
                           p50_ms=60_000.0, p99_ms=120_000.0,
                           expect_forensics=1,
                           converge_timeout_s=60.0,
                           require_xray=True),
        workers=2,
        env={"MT_FORENSIC_ERROR_RATE": "0.2",
             "MT_FORENSIC_ERROR_MIN_SAMPLES": "5",
             "MT_FORENSIC_WINDOW": "4s",
             "MT_FORENSIC_COOLDOWN": "10m"})


def watchdog_storm_scenario(duration_s: float = 24.0) -> Scenario:
    """ISSUE 18 tentpole proof: a SlowDisk latency RAMP mid-storm —
    drive 1's injected delay steps 8ms → 20ms → 45ms while the
    GET-heavy mix keeps storming — and the watchdog's
    ``drive_degrading`` rule (EWMA + robust z over the per-drive p50
    history) must fire while every latency/error SLO row still passes
    and no ``slo_burn_*`` alert exists: degradation predicted BEFORE
    any user-visible breach.  After ``drive_fast`` heals the drive the
    alert must resolve (EWMA decays back into the population).  The
    node runs 4 local drives so the drift rule has a population
    (it needs >= 3 reporting drives).  Seeded and deterministic: the
    ramp offsets are programmed, the workload is seed-driven."""
    E = _chaos.Event
    t = duration_s
    return Scenario(
        name="watchdog_storm", mix=MIXES["get_heavy_small"],
        timeline=[
            E(0.17 * t, "drive_slow", drive=1, delay_s=0.008),
            E(0.33 * t, "drive_slow", drive=1, delay_s=0.02),
            E(0.50 * t, "drive_slow", drive=1, delay_s=0.045),
            E(0.67 * t, "drive_fast", drive=1),
        ],
        duration_s=duration_s,
        budget=_slo.Budget(
            max_error_rate=0.10,
            require_watchdog=True,
            expect_alert_fired=("drive_degrading",),
            expect_alert_resolved=("drive_degrading",),
            expect_alert_quiet=("slo_burn_fast", "slo_burn_slow"),
            require_predictive=True,
            require_no_forensics=True,
            require_xray=True),
        workers=2, drives_per_node=4, watchdog=True,
        env={"MT_WATCHDOG_ENABLE": "on",
             "MT_WATCHDOG_INTERVAL": "1s"})


def burn_drill_scenario(duration_s: float = 120.0) -> Scenario:
    """The burn-rate drill: a long clean phase, then the
    forensic-drill killing blow (both node0-local drives die while
    node1's internode link 503-bursts — a genuine majority-5xx
    outage) for ~14 seconds near the end.  The FAST burn window
    (10s, compressed through the kvconfig env layer) sees a near-1.0
    error rate and must fire; the SLOW window spans the whole
    scenario, so the same burn is diluted by the clean phase to well
    under its factor and must stay quiet — the multi-window split
    working on live traffic, not seeded series.  The dilution holds
    even though the 5xx counter (and so its history series) is only
    BORN at the breach: the burn rule ratios window SUMs against the
    request series' full support, so the pre-breach clean phase
    counts as zero error mass rather than vanishing.  The firing
    alert rides the live alert_webhook sink AND bridges into the
    forensic engine (``forensic_rules=slo_burn_fast``), whose bundle
    must carry ``history.json`` with the sampled road to the breach;
    after the heal the fast window drains and the alert resolves."""
    E = _chaos.Event
    t = duration_s
    return Scenario(
        name="burn_drill", mix=MIXES["get_heavy_small"],
        timeline=[
            # the breach: ~14s of majority-5xx near the end
            E(0.800 * t, "drive_kill", drive=0),
            E(0.805 * t, "drive_kill", drive=1),
            E(0.810 * t, "burst_503", node=1),
            E(0.915 * t, "heal_link", node=1),
            E(0.920 * t, "drive_return", drive=0),
            E(0.925 * t, "drive_return", drive=1),
        ],
        duration_s=duration_s,
        # the breach IS the point: no error ceiling, forensic bundles
        # expected (the watchdog bridge + the engine's own trigger)
        budget=_slo.Budget(
            max_error_rate=1.0,
            p50_ms=60_000.0, p99_ms=120_000.0,
            converge_timeout_s=60.0,
            require_watchdog=True,
            expect_alert_fired=("slo_burn_fast",),
            expect_alert_quiet=("slo_burn_slow",),
            expect_alert_resolved=("slo_burn_fast",),
            require_history_bundle=True,
            require_xray=True),
        workers=2, watchdog=True,
        env={"MT_WATCHDOG_ENABLE": "on",
             "MT_WATCHDOG_INTERVAL": "1s",
             # compressed burn windows: the 10s fast window reads the
             # fine ring, the 3m slow window spans the whole scenario
             "MT_WATCHDOG_BURN_FAST_WINDOW": "10s",
             "MT_WATCHDOG_BURN_SLOW_WINDOW": "3m",
             "MT_WATCHDOG_SLO_OBJECTIVE": "0.035",
             "MT_WATCHDOG_FORENSIC_RULES": "slo_burn_fast",
             "MT_FORENSIC_COOLDOWN": "10m"})


def watchdog_smoke_scenario(duration_s: float = 5.0) -> Scenario:
    """The tier-1 watchdog miniature: the GET-heavy mix with the plane
    ENABLED and no chaos — the sampler must tick, the
    mt_alert_*/mt_history_* families must be on the live scrape, and
    every rule must stay quiet on a healthy cluster (the
    false-positive contract, the dual of the storms above)."""
    return Scenario(
        name="smoke_watchdog", mix=MIXES["get_heavy_small"],
        timeline=[],
        duration_s=duration_s,
        budget=_slo.Budget(
            converge_timeout_s=30.0,
            require_watchdog=True,
            expect_alert_quiet=("slo_burn_fast", "slo_burn_slow",
                                "drive_degrading"),
            require_no_forensics=True),
        watchdog=True,
        env={"MT_WATCHDOG_ENABLE": "on",
             "MT_WATCHDOG_INTERVAL": "1s"})


# the noisy tenant's mix (ISSUE 19): zipf-skewed GET/PUT over objects
# an order of magnitude larger than the well-behaved mixes — it moves
# most of the cluster's bytes (the noisy_neighbor rule's byte-share
# numerator) and its PUT churn marches the bucket into its hard quota
_NOISY_MIX = Mix("tenant_noisy",
                 {"get": 0.55, "put": 0.35, "head": 0.10},
                 sizes_bytes=(65536, 262144), key_space=12, zipf=1.2)


def tenant_storm_scenario(duration_s: float = 20.0) -> Scenario:
    """ISSUE 19 acceptance: one zipf-heavy noisy tenant (large
    objects, its bucket under a hard quota) storms beside two
    well-behaved tenants and the root mix, with the metering plane
    and the watchdog's tenant rules live.  The SLO sweep asserts the
    ``noisy_neighbor`` alert fired naming EXACTLY the noisy tenant
    (byte-share attribution from the metering counters riding the
    history rings), the innocents' client-observed p99 stayed green,
    the noisy tenant's writes were rejected with
    ``XMinioAdminBucketQuotaExceeded`` (never an innocent's), and
    rejections never dead-lettered telemetry.  No chaos timeline: the
    only "fault" is the neighbor."""
    return Scenario(
        name="tenant_storm", mix=MIXES["get_heavy_small"],
        timeline=[],
        duration_s=duration_s,
        budget=_slo.Budget(
            require_watchdog=True,
            require_metering=True,
            expect_alert_fired=("noisy_neighbor",),
            # quota 403s are 4xx — the 5xx-only tenant error counters
            # stay flat, so the burn rules must hold their silence
            expect_alert_quiet=("tenant_burn", "slo_burn_fast",
                                "slo_burn_slow"),
            expect_noisy_tenant="tenant-noisy",
            expect_quota_rejections=True,
            require_no_forensics=True),
        workers=2, watchdog=True,
        tenants=(("tenant-noisy", _NOISY_MIX, 3),
                 ("tenant-a", MIXES["get_heavy_small"], 2),
                 ("tenant-b", MIXES["get_heavy_small"], 2)),
        # above the noisy preload (~5.8 MiB: 3 workers x 12 keys x
        # ~160 KiB), crossed by its PUT churn mid-storm
        quota_bytes=12 << 20,
        env={"MT_METERING_ENABLE": "on",
             "MT_WATCHDOG_ENABLE": "on",
             "MT_WATCHDOG_INTERVAL": "1s",
             # the byte-share window reads the fine ring so the share
             # reflects the storm, not a cold start
             "MT_WATCHDOG_BURN_FAST_WINDOW": "10s",
             # CI boxes move fewer bytes than the 1 MB/s production
             # floor — the rule must still see "real" traffic
             "MT_WATCHDOG_NOISY_MIN_BPS": "200000"})


def tenant_smoke_scenario(duration_s: float = 8.0) -> Scenario:
    """The tier-1 workload-attribution miniature: one noisy tenant
    (quota'd bucket, large zipf objects) beside one innocent, sized
    for CI — same naming/quota/innocent contract as tenant_storm."""
    return Scenario(
        name="smoke_tenant", mix=MIXES["get_heavy_small"],
        timeline=[],
        duration_s=duration_s,
        budget=_slo.Budget(
            converge_timeout_s=30.0,
            require_watchdog=True,
            require_metering=True,
            expect_alert_fired=("noisy_neighbor",),
            expect_alert_quiet=("tenant_burn",),
            expect_noisy_tenant="tenant-noisy",
            expect_quota_rejections=True,
            require_no_forensics=True),
        workers=1, watchdog=True,
        tenants=(("tenant-noisy", _NOISY_MIX, 2),
                 ("tenant-a", MIXES["get_heavy_small"], 1)),
        # just above the noisy preload (2 workers x 12 keys x
        # ~160 KiB ~= 3.8 MiB) so the quota trips within seconds
        quota_bytes=5 << 20,
        env={"MT_METERING_ENABLE": "on",
             "MT_WATCHDOG_ENABLE": "on",
             "MT_WATCHDOG_INTERVAL": "1s",
             "MT_WATCHDOG_BURN_FAST_WINDOW": "10s",
             "MT_WATCHDOG_NOISY_MIN_BPS": "100000"})


# the elastic-topology mix: churn (delete + re-put) keeps minting
# "new" names after preload, which is what lets the free-space router
# actually spread writes onto a pool added mid-storm (an overwrite of
# an existing name sticks to the pool that already holds it); the
# strict digest oracle turns any byte lost or changed by a rebalance
# move into an IntegrityMismatch row
_ELASTIC_MIX = Mix("elastic_churn",
                   {"churn": 0.35, "put": 0.20, "get": 0.35,
                    "head": 0.10},
                   sizes_bytes=(2048, 16384), key_space=12,
                   verify_digest=True)


def expand_storm_scenario(duration_s: float = 15.0) -> Scenario:
    """ISSUE 16 tentpole proof: a pool is attached at 0.22t — while a
    drive is dead — and the full chaos sequence keeps firing; the SLO
    sweep then asserts the expansion is live in the manifest, the
    router actually spread new writes onto it, p99 held, heal
    converged, and the digest oracle saw identical bytes."""
    E = _chaos.Event
    t = duration_s
    return Scenario(
        name="expand_storm", mix=_ELASTIC_MIX,
        timeline=[
            E(0.08 * t, "drive_kill", drive=0),
            E(0.22 * t, "pool_add"),
            E(0.30 * t, "drive_return", drive=0),
            E(0.38 * t, "drive_slow", drive=1, delay_s=0.04),
            E(0.52 * t, "drive_fast", drive=1),
            E(0.58 * t, "partition", node=2),
            E(0.74 * t, "heal_link", node=2),
            E(0.80 * t, "burst_503", node=1),
            E(0.90 * t, "heal_link", node=1),
        ],
        duration_s=duration_s,
        budget=_slo.Budget(max_error_rate=0.10,
                           require_pool_expanded=True,
                           require_no_forensics=True,
                           converge_timeout_s=60.0,
                           require_xray=True),
        pools=True, env={"MT_REBALANCE_ENABLE": "on"})


def decommission_storm_scenario(duration_s: float = 15.0) -> Scenario:
    """The drain-under-storm variant: expand early so the churn mix
    populates the second pool, decommission it mid-chaos, and require
    the rebalancer to empty AND retire it (manifest shrinks back)
    before teardown — with the digest oracle watching every moved
    byte."""
    E = _chaos.Event
    t = duration_s
    return Scenario(
        name="decommission_storm", mix=_ELASTIC_MIX,
        timeline=[
            E(0.06 * t, "pool_add"),
            E(0.12 * t, "drive_kill", drive=0),
            E(0.30 * t, "drive_return", drive=0),
            E(0.45 * t, "pool_decommission", pool=1),
            E(0.58 * t, "partition", node=2),
            E(0.74 * t, "heal_link", node=2),
            E(0.80 * t, "burst_503", node=1),
            E(0.90 * t, "heal_link", node=1),
        ],
        duration_s=duration_s,
        budget=_slo.Budget(max_error_rate=0.10,
                           require_pool_retired=True,
                           require_no_forensics=True,
                           converge_timeout_s=60.0,
                           require_xray=True),
        pools=True, env={"MT_REBALANCE_ENABLE": "on"})


def expand_smoke_scenario(duration_s: float = 5.0) -> Scenario:
    """The tier-1 elastic miniature: drive dies, a pool is attached
    mid-traffic, the drive returns — same expansion contract as
    expand_storm, sized for CI."""
    E = _chaos.Event
    t = duration_s
    return Scenario(
        name="smoke_expand", mix=_ELASTIC_MIX,
        timeline=[E(0.15 * t, "drive_kill", drive=0),
                  E(0.30 * t, "pool_add"),
                  E(0.55 * t, "drive_return", drive=0)],
        duration_s=duration_s,
        budget=_slo.Budget(converge_timeout_s=30.0,
                           require_pool_expanded=True,
                           require_no_forensics=True),
        pools=True, env={"MT_REBALANCE_ENABLE": "on"})


def smoke_scenario(duration_s: float = 4.0) -> Scenario:
    """The tier-1 miniature: small GET-heavy mix + one drive death +
    return — same contract as the matrix, sized for CI."""
    E = _chaos.Event
    return Scenario(
        name="smoke_get_heavy",
        mix=MIXES["get_heavy_small"],
        timeline=[E(0.2 * duration_s, "drive_kill", drive=0),
                  E(0.6 * duration_s, "drive_return", drive=0)],
        duration_s=duration_s,
        budget=_slo.Budget(converge_timeout_s=30.0,
                           require_no_forensics=True,
                           require_xray=True))


def run_scenario(scenario: Scenario, base_dir: str,
                 seed: int = 1) -> list[dict]:
    """One scenario end to end on a fresh cluster; returns the SLO
    assertion rows (never raises on an SLO miss — the rows carry
    pass/fail so the matrix completes)."""
    env_all = {**_SOAK_ENV, **scenario.env}
    sink = None
    if scenario.watchdog:
        # the alert plane needs a LIVE egress endpoint before the
        # server boots; the sink's port exists only now, so it joins
        # the env here (started before the thread snapshot so its
        # accept loop never reads as a scenario leak)
        sink = _AlertSink().start()
        env_all.setdefault("MT_ALERT_WEBHOOK_ENABLE", "on")
        env_all.setdefault("MT_ALERT_WEBHOOK_ENDPOINT", sink.url)
    env_prev = {k: os.environ.get(k) for k in env_all}
    os.environ.update(env_all)
    threads_before = _slo.settled_thread_count(deadline_s=2.0)
    thread_ids = {id(t) for t in threading.enumerate()}
    tls_manager = None
    if scenario.tls:
        from ..secure import pki as _pki
        tls_manager = _pki.mint_cluster_pki(
            os.path.join(base_dir, "pki")).cert_manager()
    try:
        cluster = _chaos.SoakCluster(
            base_dir, nodes=scenario.nodes,
            drives_per_node=scenario.drives_per_node,
            backend=scenario.backend, tls=tls_manager,
            pools=scenario.pools)
        status = SoakStatus(scenario.name)
        cluster.s3.soak = status
        conv: dict | None = None
        conv_err = ""
        try:
            gen = WorkloadGenerator(
                cluster.endpoint, cluster.s3.iam.root.access_key,
                cluster.s3.iam.root.secret_key, scenario.mix,
                workers=scenario.workers, seed=seed)
            tenant_gens: list[WorkloadGenerator] = []
            if scenario.tenants:
                tenant_gens = _start_tenants(cluster, scenario, seed)
            huge: dict = {}
            huge_thread = None
            if scenario.huge_put_bytes:
                cluster.layer.make_bucket("soak-huge")
                huge_thread = threading.Thread(
                    target=_run_huge_put,
                    args=(cluster, scenario, seed, huge),
                    daemon=True, name="mt-soak-huge")
            conductor = _chaos.ChaosConductor(
                cluster, scenario.timeline).start()
            if huge_thread is not None:
                huge_thread.start()
            gen.run_for(scenario.duration_s)
            for tg in tenant_gens:
                tg.stop()
            conductor.join(timeout=scenario.duration_s + 30.0)
            if huge_thread is not None:
                huge_thread.join(timeout=scenario.duration_s + 120.0)
                if huge_thread.is_alive():
                    huge.setdefault("error", "huge PUT still running "
                                    "past the join deadline")
            # snapshot the last-minute plane NOW: its 60s window +
            # 64-sample rings would age the fault-window latencies out
            # during convergence/teardown, hollowing the p99 assertion
            api_pcts = _slo.api_percentiles(cluster.s3.api_stats)
            cluster.restore_all()
            topology = None
            if scenario.pools:
                topology = _topology_summary(
                    cluster,
                    wait_retire_s=scenario.budget.converge_timeout_s
                    if scenario.budget.require_pool_retired else 0.0)
            try:
                conv = _slo.assert_converged(
                    cluster.layer,
                    timeout_s=scenario.budget.converge_timeout_s,
                    mrf=cluster.mrf)
            except AssertionError as e:
                conv_err = str(e)
            # the watchdog verdict BEFORE the scrape: the summary
            # polls for expected resolutions (the sampler keeps
            # ticking until teardown), so the scrape then reflects
            # the settled alert state
            wdsum = None
            if scenario.watchdog:
                wdsum = _watchdog_summary(cluster, sink,
                                          scenario.budget)
            tenants_sum = _tenant_summary(scenario, tenant_gens) \
                if scenario.tenants else None
            scrape_text = _slo.scrape(cluster.endpoint)
            recorder = gen.recorder
            chaos_log = {"applied": conductor.applied,
                         "errors": conductor.errors}
            forensics = _forensic_summary(
                cluster, expect_breach=bool(
                    scenario.budget.expect_forensics))
        finally:
            cluster.stop()
        threads_after = _slo.settled_thread_count()
        leaked = _slo.leaked_thread_names(thread_ids)
        rows = _slo.evaluate(
            scenario.name, api_pcts=api_pcts, recorder=recorder,
            budget=scenario.budget, scrape_text=scrape_text,
            convergence=conv, convergence_error=conv_err,
            threads_before=threads_before, threads_after=threads_after,
            leaked=leaked, forensics=forensics, topology=topology,
            watchdog=wdsum, tenants=tenants_sum)
        if scenario.huge_put_bytes:
            rows.append({
                "scenario": scenario.name,
                "metric": "huge_put_byte_correct",
                "value": 1 if huge.get("ok") else 0, "unit": "bool",
                "passed": bool(huge.get("ok")), "detail": huge})
        if scenario.tls:
            # the encrypted planes must actually have carried the
            # storm: live handshakes on the scrape, or the scenario
            # silently ran plaintext and proved nothing
            shakes = _slo.metric_total(scrape_text,
                                       "mt_tls_handshake_total")
            rows.append({
                "scenario": scenario.name, "metric": "tls_engaged",
                "value": shakes, "unit": "handshakes",
                "passed": shakes > 0,
                "detail": {"failed": _slo.metric_total(
                    scrape_text, "mt_tls_handshake_failed_total")}})
        # context rows: what actually ran (not assertions; always pass)
        rows.append({"scenario": scenario.name, "metric": "ops_total",
                     "value": recorder.ops(), "unit": "ops",
                     "passed": True,
                     "detail": {"per_api": recorder.summary(),
                                "chaos": chaos_log,
                                "tenants": tenants_sum,
                                "duration_s": scenario.duration_s,
                                "seed": seed}})
        status.finish(rows)
        return rows
    finally:
        if sink is not None:
            sink.stop()
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _forensic_summary(cluster, expect_breach: bool = False) -> dict:
    """The forensic-plane verdict for one finished scenario: bundle
    count from the node's engine, and (for the drill) whether the
    newest bundle actually holds the breach window's request records
    — 5xx completions in the flight-recorder error ring."""
    fx = getattr(cluster.s3, "forensic", None)
    if fx is None:
        return {"dumped": 0, "engine": "disabled"}
    fx.join(timeout=15.0)        # an in-flight bundle write finishes
    bundles = fx.bundles()
    out = {"dumped": len(bundles), "dir": fx.dir,
           "bundles": [b["name"] for b in bundles]}
    if expect_breach and bundles:
        import json as _json
        import zipfile as _zip
        try:
            with _zip.ZipFile(os.path.join(
                    fx.dir, bundles[-1]["name"])) as z:
                doc = _json.loads(z.read("flightrec.json"))
            breach = [r for r in doc.get("errors", [])
                      if r.get("status", 0) >= 500]
            out["breach_records_ok"] = len(breach) > 0
            out["breach_records"] = len(breach)
            # ISSUE 15 acceptance: every request on the live 3-node
            # cluster carries a COMPLETE stage timeline — the serial
            # vector (incl. ``other``) reconciles with the duration
            recs = [r for r in doc.get("requests", [])
                    if r.get("stages")]
            out["stage_timeline_ok"] = bool(recs) and all(
                sum(r["stages"].values()) == r["durationNs"]
                for r in recs)
            # ISSUE 17: the bundle must also carry ASSEMBLED causal
            # trees for the breach window's requests (tracetrees.json,
            # obs/tracetree.py) — roots whose request IDs come from the
            # same error ring the breach records do
            with _zip.ZipFile(os.path.join(
                    fx.dir, bundles[-1]["name"])) as z:
                tdoc = _json.loads(z.read("tracetrees.json"))
            trees = tdoc.get("trees", [])
            breach_rids = {r.get("requestID") for r in breach}
            tree_rids = {t.get("requestID") for t in trees}
            out["trace_trees_ok"] = bool(trees) and \
                bool(breach_rids & tree_rids)
            out["trace_trees"] = len(trees)
        except Exception as e:  # noqa: BLE001 — verdict rides the row
            out["breach_records_ok"] = False
            out["error"] = f"{type(e).__name__}: {e}"
    return out


def _start_tenants(cluster, scenario: Scenario,
                   seed: int) -> list[WorkloadGenerator]:
    """Mint one IAM user + bucket + generator per scenario tenant and
    start them.  The FIRST tenant is the noisy one: when
    ``quota_bytes`` is set its bucket gets a HARD quota through the
    live admin surface (the same signed route ``mc admin bucket quota``
    uses), so enforcement under storm rides the real
    kvconfig+bucket-metadata path, not a test double."""
    from ..admin.client import AdminClient
    admin = AdminClient(cluster.endpoint,
                        cluster.s3.iam.root.access_key,
                        cluster.s3.iam.root.secret_key)
    gens: list[WorkloadGenerator] = []
    for i, (name, mix, workers) in enumerate(scenario.tenants):
        cluster.s3.iam.add_user(name, f"{name}-secret-key",
                                policies=["readwrite"])
        bucket = f"soak-t-{name.replace('_', '-')}"
        cluster.layer.make_bucket(bucket)
        if i == 0 and scenario.quota_bytes:
            admin.set_bucket_quota(bucket, scenario.quota_bytes)
        gens.append(WorkloadGenerator(
            cluster.endpoint, name, f"{name}-secret-key", mix,
            workers=workers, seed=seed + i + 1, bucket=bucket))
    for g in gens:
        g.start()
    return gens


def _tenant_summary(scenario: Scenario,
                    gens: list[WorkloadGenerator]) -> dict:
    """Per-tenant client-observed verdicts for the Budget's tenant
    rows: op/error counts, error codes (the quota rows key on
    ``XMinioAdminBucketQuotaExceeded``), and GET/PUT p99."""
    out: dict = {}
    for (name, _mix, _workers), g in zip(scenario.tenants, gens):
        r = g.recorder
        out[name] = {
            "bucket": g.bucket,
            "ops": r.ops(),
            "errors": r.error_count(),
            "error_codes": dict(r.error_codes),
            "p99_get_ms": round(
                r.percentile("GetObject", 0.99) / 1e6, 2),
            "p99_put_ms": round(
                r.percentile("PutObject", 0.99) / 1e6, 2),
        }
    return out


class _AlertSink:
    """Minimal live HTTP endpoint for the ``alert_webhook`` egress
    target: the watchdog scenarios assert alert events actually rode
    the store-and-forward plane onto a real wire, not just an
    in-process callback.  One JSON body per POST (the HTTPLogTarget
    shape)."""

    def __init__(self):
        import http.server
        sink = self

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0) or 0)
                body = self.rfile.read(n)
                try:
                    sink.events.append(json.loads(body))
                except ValueError:
                    sink.events.append(
                        {"raw": body.decode("utf-8", "replace")})
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def log_message(self, *args):
                pass

        self.events: list[dict] = []
        self._srv = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), _Handler)
        self._thread: threading.Thread | None = None

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self._srv.server_address[1]}"

    def start(self) -> "_AlertSink":
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True,
            name="mt-soak-alert-sink")
        self._thread.start()
        return self

    def stop(self) -> None:
        try:
            self._srv.shutdown()
            self._srv.server_close()
        except Exception:  # noqa: BLE001 — teardown must finish
            pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)


def _watchdog_summary(cluster, sink: _AlertSink, budget) -> dict:
    """The watchdog plane's verdict for one finished scenario: rule
    transition counts, first-firing/last-resolution timestamps, live
    sink deliveries, and (for bridge scenarios) the newest forensic
    bundle's ``history.json``.  Polls briefly for expected
    resolutions — the sampler keeps ticking until teardown, and EWMA
    decay / window drain need a few intervals to un-breach."""
    wd = getattr(cluster.s3, "watchdog", None)
    if wd is None:
        return {"enabled": False}
    want_resolved = tuple(budget.expect_alert_resolved)
    deadline = time.monotonic() + 45.0
    while want_resolved and time.monotonic() < deadline:
        live = {a["rule"] for a in wd.alerts()["active"]
                if a["state"] == "firing"}
        if not any(r in live for r in want_resolved):
            break
        time.sleep(0.25)
    # alert events ride the egress sender thread — give the queue a
    # moment to drain into the sink
    deadline = time.monotonic() + 10.0
    while budget.expect_alert_fired and not sink.events and \
            time.monotonic() < deadline:
        time.sleep(0.1)
    doc = wd.alerts()
    fired: dict = {}
    resolved: dict = {}
    for (rule, to), n in dict(wd.transitions).items():
        if to == "firing":
            fired[rule] = fired.get(rule, 0) + n
        elif to == "resolved":
            resolved[rule] = resolved.get(rule, 0) + n
    fired_at: dict = {}
    resolved_at: dict = {}
    # which SUBJECTS each rule fired for — the tenant rows assert
    # noisy_neighbor named the right tenant, not just that it fired
    subjects_by_rule: dict = {}
    for a in list(doc["active"]) + list(doc["recent"]):
        rule = a["rule"]
        subjects_by_rule.setdefault(rule, []).append(a["subject"])
        at = a.get("firedAt")
        if at is not None and at < fired_at.get(rule, float("inf")):
            fired_at[rule] = at
        if a.get("resolvedAt") is not None:
            resolved_at[rule] = a["resolvedAt"]
    burn_at = min((at for rule, at in fired_at.items()
                   if rule.startswith("slo_burn")), default=None)
    drive_at = fired_at.get("drive_degrading")
    by_state: dict = {}
    by_rule: dict = {}
    for ev in list(sink.events):
        st, rl = ev.get("state", "?"), ev.get("rule", "?")
        by_state[st] = by_state.get(st, 0) + 1
        by_rule[rl] = by_rule.get(rl, 0) + 1
    out = {
        "enabled": True,
        "evals": sum(wd.evals.values()),
        "interval_s": wd.sampler.interval_s,
        "fired": fired, "resolved": resolved,
        "fired_at": fired_at, "resolved_at": resolved_at,
        "subjects_by_rule": subjects_by_rule,
        "predictive": drive_at is not None and
        (burn_at is None or drive_at < burn_at),
        "delivered": len(sink.events),
        "delivered_by_state": by_state,
        "delivered_by_rule": by_rule,
        "active": [(a["rule"], a["subject"], a["state"])
                   for a in doc["active"]],
        "history": wd.history.stats(),
    }
    if budget.require_history_bundle:
        out["history_bundle"] = _history_bundle_check(cluster)
    return out


def _history_bundle_check(cluster) -> dict:
    """Open the newest forensic bundle and read ``history.json`` —
    the firing→forensic bridge's acceptance: the bundle carries the
    sampled road to the breach, not just the instant."""
    import zipfile as _zip
    fx = getattr(cluster.s3, "forensic", None)
    if fx is None:
        return {"enabled": False, "error": "no forensic engine"}
    fx.join(timeout=15.0)
    bundles = fx.bundles()
    if not bundles:
        return {"enabled": False, "bundles": 0}
    try:
        with _zip.ZipFile(os.path.join(fx.dir,
                                       bundles[-1]["name"])) as z:
            doc = json.loads(z.read("history.json"))
        return {"enabled": bool(doc.get("enabled")),
                "bundles": len(bundles),
                "bundle": bundles[-1]["name"],
                "series": len(doc.get("series", []))}
    except Exception as e:  # noqa: BLE001 — verdict rides the row
        return {"enabled": False, "bundles": len(bundles),
                "error": f"{type(e).__name__}: {e}"}


def _topology_summary(cluster, wait_retire_s: float = 0.0) -> dict:
    """Elastic-topology verdict for one finished pools-mode scenario:
    live pool count, per-pool object residency, rebalance counters and
    manifest version.  With ``wait_retire_s`` the summary first gives
    the rebalancer (faults are healed by now) that long to finish
    draining and retire decommissioned pools — kicked each poll so the
    drain never sits out an interval."""
    from ..objectlayer.pools import STATUS_DRAINING
    layer = cluster.layer
    rb = cluster.rebalancer
    if wait_retire_s > 0:
        deadline = time.monotonic() + wait_retire_s
        while time.monotonic() < deadline and any(
                sp.status == STATUS_DRAINING for sp in layer.specs):
            if rb is not None:
                rb.kick()
            time.sleep(0.25)
    per_pool = []
    for p in layer.pools:
        n = 0
        for b in layer.list_buckets():
            n += len(p.list_object_versions(b.name))
        per_pool.append(n)
    st = rb.stats if rb is not None else None
    return {
        "pools": len(layer.pools),
        "statuses": [sp.status for sp in layer.specs],
        "per_pool_objects": per_pool,
        "new_pool_objects": per_pool[-1] if len(per_pool) > 1 else 0,
        "retired": len(layer.pools) == 1 and not any(
            sp.status == STATUS_DRAINING for sp in layer.specs),
        "moved_objects": st.moved_objects if st else 0,
        "moved_bytes": st.moved_bytes if st else 0,
        "move_failures": st.failed if st else 0,
        "manifest_version": layer._manifest_version,
    }


class _SeededBody:
    """File-like deterministic body generator: chunks are produced
    lazily from the seed and digested as they stream OUT, so the drill
    holds O(chunk) of the object — the whole point of a 1 GiB drill in
    the same plane other scenarios run under a 256 MiB watermark."""

    def __init__(self, seed: int, nbytes: int):
        import hashlib

        import numpy as np
        self._rng = np.random.default_rng(seed)
        self._np = np
        self.left = nbytes
        self.md5 = hashlib.md5()

    def read(self, n: int) -> bytes:
        take = min(int(n), self.left)
        if take <= 0:
            return b""
        b = self._rng.integers(0, 256, take,
                               dtype=self._np.uint8).tobytes()
        self.left -= take
        self.md5.update(b)
        return b


def _run_huge_put(cluster, scenario: Scenario, seed: int,
                  out: dict) -> None:
    """The huge_put drill body (its own ``mt-soak-huge`` thread):
    sleep to mid-chaos, stream one ``huge_put_bytes`` object into the
    layer (mesh-sharded on a mesh-backend cluster — the scaled stream
    batch spreads its stripes over the whole device axis), then read
    it back range by range and compare digests.  Both legs hold
    O(chunk) memory.  Results land in ``out`` for the huge_put
    assertion row."""
    import hashlib
    time.sleep(0.3 * scenario.duration_s)
    nbytes = scenario.huge_put_bytes
    chunk = 8 << 20
    try:
        src = _SeededBody(seed, nbytes)
        t0 = time.monotonic()
        cluster.layer.put_object("soak-huge", "huge-object", src)
        put_s = time.monotonic() - t0
        want = src.md5.hexdigest()
        got = hashlib.md5()
        t1 = time.monotonic()
        off = 0
        while off < nbytes:
            _, seg = cluster.layer.get_object(
                "soak-huge", "huge-object", offset=off,
                length=min(chunk, nbytes - off))
            got.update(seg)
            off += len(seg) or chunk
        get_s = time.monotonic() - t1
        ok = got.hexdigest() == want
        out.update(ok=ok, bytes=nbytes, put_s=round(put_s, 3),
                   get_s=round(get_s, 3),
                   put_GiBps=round(nbytes / put_s / 2**30, 3)
                   if put_s > 0 else None)
        if not ok:
            out["error"] = "GET bytes differ from PUT body"
    except Exception as e:  # noqa: BLE001 — the row carries the failure
        out.update(ok=False, bytes=nbytes,
                   error=f"{type(e).__name__}: {e}")


def run_matrix(scenarios: list[Scenario] | None = None,
               out_path: str = "SOAK_r01.json",
               base_dir: str | None = None, seed: int = 1) -> dict:
    """Run the scenario matrix sequentially and write the report."""
    scenarios = scenarios if scenarios is not None else default_matrix()
    rows: list[dict] = []
    root = base_dir or tempfile.mkdtemp(prefix="soak-")
    for i, sc in enumerate(scenarios):
        rows.extend(run_scenario(sc, os.path.join(root, f"s{i}"),
                                 seed=seed))
    report = {
        "report": "soak",
        "scenarios": [sc.name for sc in scenarios],
        "passed": sum(1 for r in rows if r["passed"]),
        "failed": sum(1 for r in rows if not r["passed"]),
        "rows": rows,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return report
