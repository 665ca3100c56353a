"""The five ``commit_*`` per-layer metrics (PR 30) as data: each file
loads through ``manifest.Cell`` in every cell and reads what it says from
two synthetic scrapes; from a scrape of a program without the families
(the parent commit) each reads nothing and is left out.  Run by hand with
the rest:

    python -m pytest benchmarks/tests -q
"""

import pytest

from benchmarks.harness import manifest, readers
from benchmarks.harness.deploy import parse_scrape

# per PUT on 16 drives, batches of 4 ops: 16 drive ops; each waits 0.5 s,
# runs 20 ms of body; a batch's flush takes 48 ms (12 ms per op) and
# issues 21 fsyncs (5 per op + the shared bucket dir) in 3 waves
OPS, QUEUE_S, BODY_S = 16, 0.5, 0.020
BATCHES, FLUSH_S, FSYNCS, WAVES = 4, 0.048, 21, 3
WANT = {"commit_queue_ms": 500.0, "commit_body_ms": 20.0,
        "commit_flush_ms": 12.0, "commit_fsyncs_per_put": 84.0,
        "commit_flush_width": 7.0}


def _scrape(puts: int, base: float) -> dict:
    n = base + puts
    lines = [f'mt_s3_requests_api_total{{api="PutObject"}} {n}',
             f'mt_s3_requests_api_total{{api="GetObject"}} {3 * n}',
             f'mt_commit_queue_seconds_sum {QUEUE_S * OPS * n}',
             f'mt_commit_queue_seconds_count {OPS * n}',
             f'mt_commit_body_seconds_sum {BODY_S * OPS * n}',
             f'mt_commit_body_seconds_count {OPS * n}',
             f'mt_commit_flush_seconds_sum {FLUSH_S * BATCHES * n}',
             f'mt_commit_flush_seconds_count {BATCHES * n}',
             f'mt_commit_fsyncs_total {FSYNCS * BATCHES * n}',
             f'mt_commit_flush_waves_total {WAVES * BATCHES * n}',
             f'mt_commit_group_fsyncs_saved_total {3 * BATCHES * n}']
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _cells():
    m = manifest.load_manifest()
    return [manifest.Cell(m, w["name"]) for w in m["workloads"]]


@pytest.mark.parametrize("name", sorted(WANT))
def test_commit_metric_loads_in_every_cell_and_reads_its_family(name):
    ctx = {"scrape0": _scrape(0, 40), "scrape1": _scrape(10, 40)}
    for cell in _cells():
        spec = next((e for e in cell.per_layer if e["name"] == name), None)
        assert spec is not None, f"{cell.name} does not report {name}"
        assert spec["moves"] == "ops_per_s"
        assert spec["layer"] == "writer plane + commit"
        assert spec["reader"]["kind"] == "counter"
        assert readers.read(spec, ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_commit_metric_reads_nothing_from_a_program_without_it(name):
    """The driver lays these files over the parent's checkout too: its
    scrape has none of the families and the line leaves the metric out."""
    def parent(n):
        s = _scrape(n, 40)
        return {fam: v for fam, v in s.items()
                if not fam.startswith("mt_commit_")
                or fam == "mt_commit_group_fsyncs_saved_total"}
    ctx = {"scrape0": parent(0), "scrape1": parent(10)}
    spec = next(e for e in _cells()[0].per_layer if e["name"] == name)
    assert readers.read(spec, ctx) is None
