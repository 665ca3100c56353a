"""BENCHMARK.json and the data files it names.

The manifest holds names; everything that belongs to one cell, one
configuration, one traffic mix or one metric sits in a file of its own
under ``benchmarks/``, found by that name.  A later PR adds files and
manifest entries and edits nothing here.
"""

from __future__ import annotations

import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _need(cond, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def check_name(s, what: str) -> str:
    _need(isinstance(s, str) and NAME_RE.match(s),
          f"{what}: {s!r} is not a name (letters, digits, _ . - ; at most "
          f"64; starts with a letter, digit or _)")
    return s


def check_unit(s, what: str) -> str:
    _need(isinstance(s, str) and UNIT_RE.match(s),
          f"{what}: {s!r} is not a unit (1-16 of letters, digits, "
          f"_ / % . -)")
    return s


def check_line(s, what: str) -> str:
    _need(isinstance(s, str) and 1 <= len(s) <= 200
          and "\n" not in s and "\t" not in s and "\r" not in s,
          f"{what}: needs 1-200 characters on one line, no tab")
    return s


def _keys(entry: dict, required: set, optional: set, what: str) -> None:
    _need(isinstance(entry, dict), f"{what}: not an object")
    got = set(entry)
    _need(required <= got, f"{what}: lacks {sorted(required - got)}")
    _need(got <= required | optional,
          f"{what}: has unknown keys {sorted(got - required - optional)}")


def validate(m: dict) -> dict:
    """The contract's rules that can be checked without a run.  Raises
    ManifestError; returns ``m``."""
    _keys(m, {"command", "paths", "run_seconds", "configs", "workloads",
              "end_to_end", "per_layer"}, set(), "BENCHMARK.json")
    _need(isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32,
          "command: 1-32 strings")
    for w in m["command"]:
        check_line(w, "command word")
        _need(not w.startswith("/") and ".." not in w.split("/"),
              f"command word {w!r} leads out of the repo")
    _need(isinstance(m["paths"], list) and 1 <= len(m["paths"]) <= 16,
          "paths: 1-16 directories")
    for p in m["paths"]:
        _need(isinstance(p, str) and re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
              and not p.startswith("/") and ".." not in p.split("/"),
              f"path {p!r}")
    rs = m["run_seconds"]
    _need(isinstance(rs, int) and not isinstance(rs, bool)
          and 1 <= rs <= 51, "run_seconds: a whole number from 1 to 51")

    _need(1 <= len(m["configs"]) <= 24, "configs: 1-24")
    cfg_names, cfg_files = set(), set()
    for c in m["configs"]:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(),
              f"config {c.get('name')!r}")
        check_name(c["name"], "config name")
        _need(c["name"] not in cfg_names, f"config {c['name']} twice")
        cfg_names.add(c["name"])
        check_line(c["source"], f"config {c['name']} source")
        check_line(c["why"], f"config {c['name']} why")
        _need(any(c["file"].startswith(p.rstrip("/") + "/")
                  for p in m["paths"]),
              f"config file {c['file']} is outside paths")
        _need(c["file"] not in cfg_files, f"config file {c['file']} twice")
        cfg_files.add(c["file"])
        _need(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16,
              "reduced: at most 16 keys")
        for k in c["reduced"]:
            check_name(k, f"config {c['name']} reduced key")

    _need(2 <= len(m["workloads"]) <= 24, "workloads: 2-24 cells")
    cells, pairs, used = set(), set(), set()
    for w in m["workloads"]:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(),
              f"workload {w.get('name')!r}")
        check_name(w["name"], "workload name")
        check_name(w["config"], "workload config")
        check_name(w["traffic"], "workload traffic")
        check_line(w["why"], f"workload {w['name']} why")
        _need(w["name"] not in cells, f"workload {w['name']} twice")
        cells.add(w["name"])
        _need(w["config"] in cfg_names,
              f"workload {w['name']}: no config {w['config']}")
        used.add(w["config"])
        _need((w["config"], w["traffic"]) not in pairs,
              f"pair {w['config']}/{w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
        _need(w["chips"] in (1, 4), f"workload {w['name']}: chips 1 or 4")
    _need(used == cfg_names, f"configs without a cell: "
          f"{sorted(cfg_names - used)}")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    _need(four <= max(1, len(m["workloads"]) // 2),
          "too many four-chip cells")

    names = set()
    _need(1 <= len(m["end_to_end"]) <= 16, "end_to_end: 1-16")
    _need(1 <= len(m["per_layer"]) <= 128, "per_layer: 1-128")
    e2e = set()
    for e in m["end_to_end"]:
        _keys(e, {"name", "unit", "better", "bound", "source"},
              {"workloads"}, f"metric {e.get('name')!r}")
        _need(e["source"] in ("host_clock", "device_trace"),
              f"end-to-end metric {e['name']}: source {e['source']}")
        _need(isinstance(e["bound"], (int, float))
              and 0.01 <= e["bound"] <= 0.25,
              f"metric {e['name']}: bound from 0.01 to 0.25")
        e2e.add(e["name"])
    _need("setup_s" in e2e, "end_to_end lacks setup_s")
    for e in m["per_layer"]:
        _keys(e, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, f"metric {e.get('name')!r}")
        _need(e["source"] in SOURCES, f"metric {e['name']}: source")
        check_line(e["layer"], f"metric {e['name']} layer")
        _need(e["moves"] in e2e,
              f"metric {e['name']} moves {e['moves']!r}, no such "
              f"end-to-end metric")
    for e in m["end_to_end"] + m["per_layer"]:
        check_name(e["name"], "metric name")
        check_unit(e["unit"], f"metric {e['name']} unit")
        _need(e["better"] in ("lower", "higher"),
              f"metric {e['name']}: better is lower or higher")
        _need(e["name"] not in names, f"metric {e['name']} twice")
        names.add(e["name"])
        for c in e.get("workloads", []):
            _need(c in cells, f"metric {e['name']}: no cell {c}")
    return m


def load_manifest(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "rb") as f:
        raw = f.read()
    _need(len(raw) <= 64 * 1024, "BENCHMARK.json is over 64 KiB")
    return validate(json.loads(raw))


def load_data(kind: str, name: str) -> dict:
    """benchmarks/<kind>/<name>.json, found by name."""
    check_name(name, kind)
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    try:
        with open(path) as f:
            d = json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"no file {os.path.relpath(path, ROOT)} for "
                            f"{kind} {name!r}") from None
    _need(d.get("name") == name, f"{path}: its name is {d.get('name')!r}")
    return d


def check_config(cfg: dict) -> dict:
    """A configuration file's ``down_drives``, where it has the key:
    indices into its ``drives``, all distinct, at least one and at most
    ``parity_shards``, leaving the write quorum alive, and the guarantee
    ``shards_expected_on_healthy_drives`` equal to the drives that stay.
    A file without the key is returned as it is.  Raises ManifestError;
    returns ``cfg``."""
    if "down_drives" not in cfg:
        return cfg
    down, name = cfg["down_drives"], cfg.get("name")
    n, m = cfg["drives"], cfg["fixes"]["parity_shards"]
    g = cfg["guarantees"]
    _need(isinstance(down, list) and all(
        isinstance(i, int) and not isinstance(i, bool) and 0 <= i < n
        for i in down), f"config {name}: down_drives {down!r} are not "
        f"indices into its {n} drives")
    _need(len(set(down)) == len(down),
          f"config {name}: down_drives {down} name a drive twice")
    _need(1 <= len(down) <= m, f"config {name}: {len(down)} down_drives, "
          f"from 1 to parity_shards = {m}")
    _need(n - len(down) >= g["write_quorum"],
          f"config {name}: {n - len(down)} live drives are under the write "
          f"quorum {g['write_quorum']}")
    _need(g["shards_expected_on_healthy_drives"] == n - len(down),
          f"config {name}: shards_expected_on_healthy_drives is "
          f"{g['shards_expected_on_healthy_drives']}, the drives that stay "
          f"are {n - len(down)}")
    return cfg


def metrics_for(m: dict, section: str, cell: str) -> list[dict]:
    """The manifest's metrics of one section that this cell reports."""
    return [e for e in m[section]
            if "workloads" not in e or cell in e["workloads"]]


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, manifest: dict, name: str):
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        _need(entry is not None, f"BENCHMARK.json has no workload {name!r}")
        self.name = name
        self.chips = entry["chips"]
        self.file = load_data("workloads", name)
        for k in ("config", "traffic", "chips"):
            _need(self.file.get(k) == entry[k],
                  f"workloads/{name}.json and BENCHMARK.json differ on {k}")
        self.config = check_config(load_data("configs", entry["config"]))
        self.traffic = load_data("traffic", entry["traffic"])
        self.end_to_end = [dict(e, **load_data("end_to_end", e["name"]))
                           for e in metrics_for(manifest, "end_to_end", name)]
        self.per_layer = [dict(e, **load_data("layer_metrics", e["name"]))
                          for e in metrics_for(manifest, "per_layer", name)]
