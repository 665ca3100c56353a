"""The per-layer metrics PR 23 added as data: each file loads through
``manifest.Cell`` in every cell that has its leg (``hash_launch_ms`` only
where a body ends in a tail block) and reads what it says from two synthetic
scrapes; from a scrape of a program that lacks the legs (the parent
commit) each reads nothing and is left out.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q
"""

import pytest

from benchmarks.harness import manifest, readers
from benchmarks.harness.deploy import parse_scrape
from benchmarks.tests.tail_cells import has_tail_block

# 10 PUTs of 10 MiB at 12+4 in the window; seconds and bytes per PUT as
# the code moves them (PERF.md section 6, PR 23): data up, parity down,
# data and parity up again to be hashed, digests down
UP_RS, DOWN_RS = 12 * 873856, 4 * 873856
UP_HH, DOWN_HH = 16 * 873814, 16 * 32
LEGS = {("encode", "prep"): 0.030, ("encode", "upload"): 0.004,
        ("encode", "launch"): 0.006, ("encode", "fetch"): 0.050,
        ("encode", "dispatch"): 0.090, ("encode", "batch"): 0.080,
        ("hash", "prep"): 0.020, ("hash", "upload"): 0.005,
        ("hash", "launch"): 3.0, ("hash", "fetch"): 0.2,
        ("hash", "frame"): 0.015, ("hash", "dispatch"): 3.3,
        ("decode", "fetch"): 9.0, ("decode", "launch"): 9.0}
WANT = {"put_md5_ms": 120.0, "codec_prep_ms": 50.0, "codec_upload_ms": 9.0,
        "rs_launch_ms": 6.0, "hash_launch_ms": 3000.0,
        "codec_fetch_ms": 250.0, "bitrot_frame_ms": 15.0,
        "link_bytes_per_byte": (UP_RS + DOWN_RS + UP_HH + DOWN_HH)
        / 10485760}


def _scrape(puts: int, base: float) -> dict:
    """A server's scrape after ``puts`` PUTs on top of ``base`` earlier
    ones (other APIs, other ops and the serial/async split are there to
    be left out or summed)."""
    n = base + puts
    lines = [f'mt_s3_requests_api_total{{api="PutObject"}} {n}',
             f'mt_s3_requests_api_total{{api="GetObject"}} {3 * n}',
             f'mt_s3_stage_seconds_sum{{api="PutObject",stage="md5",'
             f'vec="async"}} {0.120 * n}',
             f'mt_s3_stage_seconds_sum{{api="PutObject",stage="encode",'
             f'vec="serial"}} {3.6 * n}',
             f'mt_s3_stage_seconds_sum{{api="UploadPart",stage="md5",'
             f'vec="async"}} {7.0 * n}',
             f'mt_tpu_bytes_total{{backend="tpu",op="encode"}} '
             f'{10485760 * n}',
             f'mt_tpu_bytes_total{{backend="tpu",op="hash"}} '
             f'{UP_HH * n}']
    for (op, leg), s in LEGS.items():
        lines.append(f'mt_tpu_leg_seconds_sum{{leg="{leg}",op="{op}"}} '
                     f'{s * n}')
        lines.append(f'mt_tpu_leg_seconds_count{{leg="{leg}",op="{op}"}} '
                     f'{2 * n}')
    for op, d, b in (("encode", "h2d", UP_RS), ("encode", "d2h", DOWN_RS),
                     ("hash", "h2d", UP_HH), ("hash", "d2h", DOWN_HH),
                     ("decode", "h2d", 5e9)):
        lines.append(f'mt_tpu_link_bytes_total{{dir="{d}",op="{op}"}} '
                     f'{b * n}')
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _cells():
    m = manifest.load_manifest()
    return [manifest.Cell(m, w["name"]) for w in m["workloads"]]


def _reports(cell: manifest.Cell, name: str) -> bool:
    """Whether the cell has the metric's leg: ``hash_launch_ms`` is the
    tail block's second hash program; every other leg, every cell."""
    return name != "hash_launch_ms" or has_tail_block(cell)


@pytest.mark.parametrize("name", sorted(WANT))
def test_leg_metric_loads_in_every_cell_and_reads_its_legs(name):
    ctx = {"scrape0": _scrape(0, 40), "scrape1": _scrape(10, 40)}
    cells = _cells()
    assert any(_reports(c, name) for c in cells)
    for cell in cells:
        spec = next((e for e in cell.per_layer if e["name"] == name), None)
        if not _reports(cell, name):
            assert spec is None, f"{cell.name} reports {name}"
            continue
        assert spec is not None, f"{cell.name} does not report {name}"
        assert spec["moves"] == "ops_per_s"
        assert spec["reader"]["kind"] in ("stage", "counter")
        assert readers.read(spec, ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_leg_metric_reads_nothing_from_a_program_without_legs(name):
    """The driver lays these files over the parent's checkout too: its
    scrape has no leg family, no link counter and no md5 stage, and the
    line must simply leave the metric out."""
    def parent(n):
        s = _scrape(n, 40)
        for fam in ("mt_tpu_leg_seconds_sum", "mt_tpu_leg_seconds_count",
                    "mt_tpu_link_bytes_total"):
            del s[fam]
        s["mt_s3_stage_seconds_sum"] = [
            (lab, v) for lab, v in s["mt_s3_stage_seconds_sum"]
            if lab["stage"] != "md5"]
        return s
    ctx = {"scrape0": parent(0), "scrape1": parent(10)}
    spec = next(e for c in _cells() for e in c.per_layer
                if e["name"] == name)
    assert readers.read(spec, ctx) is None
