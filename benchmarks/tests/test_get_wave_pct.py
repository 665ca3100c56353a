"""``get_wave_pct`` as data: the file loads through ``manifest.Cell`` in
the three read cells its entry lists and in no other, the manifest
validates with it appended after every entry it had, and it reads the
hand-computed share from two synthetic scrapes; from a scrape of a
program without the family (the parent commit) it reads nothing and the
line leaves it out.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q -k get_wave
"""

import pytest

from benchmarks.harness import manifest, readers
from benchmarks.harness.deploy import parse_scrape

MIXED = ["n16.mixed-10m", "n16.small-zipf", "d4x4.mixed-10m"]


def _scrape(windows: int, wave: int, pool: int | None) -> dict:
    """The cumulative scrape after ``windows`` windows, each with 80
    rounds of a GET's shard read over 12 drives: ``wave`` of each round's
    drives read in the wave, ``pool`` by pool children (None: no such
    series)."""
    lines = [f'mt_s3_requests_api_total{{api="GetObject"}} {80 * windows}',
             f'mt_read_get_drives_total{{route="wave"}} '
             f'{80 * wave * windows}']
    if pool is not None:
        lines.append(f'mt_read_get_drives_total{{route="pool"}} '
                     f'{80 * pool * windows}')
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _spec(cell: str) -> dict | None:
    c = manifest.Cell(manifest.load_manifest(), cell)
    return next((e for e in c.per_layer if e["name"] == "get_wave_pct"),
                None)


@pytest.mark.parametrize("wave,pool,want", [
    (12, None, 100.0),      # one node, every drive local
    (12, 0, 100.0),
    (3, 9, 25.0),           # a node of d4x4: 3 of its 12 shards its own
    (11, 1, 100 * 11 / 12),  # one drive offline: a pool child
])
def test_get_wave_pct_reads_the_waved_share(wave, pool, want):
    ctx = {"scrape0": _scrape(3, wave, pool),
           "scrape1": _scrape(5, wave, pool)}
    for cell in MIXED:
        assert readers.read(_spec(cell), ctx) == pytest.approx(want)


def test_get_wave_pct_reads_nothing_from_a_program_without_it():
    ctx = {"scrape0": {}, "scrape1": {"mt_s3_requests_api_total": [
        ({"api": "GetObject"}, 400.0)]}}
    assert readers.read(_spec("n16.small-zipf"), ctx) is None


def test_get_wave_pct_loads_in_the_read_cells_only():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        spec = _spec(w["name"])
        if w["name"] not in MIXED:
            assert spec is None, w["name"]
            continue
        assert spec["layer"] == "read fan-out + verify"
        assert spec["moves"] == "ops_per_s"
        assert spec["unit"] == "%"


def test_manifest_validates_with_get_wave_pct_appended():
    m = manifest.load_manifest()
    names = [e["name"] for e in m["per_layer"]]
    assert names.index("get_wave_pct") == len(names) - 1
    assert names.index("get_wave_pct") > names.index("rs_fused_group_roofline")
    entry = m["per_layer"][names.index("get_wave_pct")]
    assert entry["workloads"] == MIXED
    manifest.validate(m)
