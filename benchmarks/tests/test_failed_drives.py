"""A configuration with failed drives: the plain reference's decode, the
loader's rules for ``down_drives``, the on-disk oracle on layouts the
program wrote on the CPU (healthy, a drive failed after the writes, and
three faults it has to catch), and CPU rehearsals of a test-only
configuration (``down/``: ``n4-ec2p2`` with drive 1 of 4 failed after the
preload), run from a copy of the checkout whose ``BENCHMARK.json`` names
it: one that passes, and one each with the program broken underneath (a
rebuilt shard, a PUT's parity shard altered where it is produced) that
must not.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q
"""

import copy
import itertools
import json
import os
import random
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import manifest, oracle, reference
from benchmarks.harness.deploy import Deployment, Failed

HERE = os.path.dirname(os.path.abspath(__file__))
DOWN = os.path.join(HERE, "down")
TEST_CONFIG = "n4-ec2p2-1down"
TEST_CELL = "n4d1.mixed-10m"
BUCKET = "oracle"
BS = 1 << 20


# -- reference.decode ---------------------------------------------------------

def _full(k: int, m: int, n: int, seed: int) -> np.ndarray:
    data = np.random.default_rng(seed).integers(0, 256, (k, n),
                                                dtype=np.uint8)
    return np.concatenate([data, reference.encode_parity(data, m)])


def _patterns(k: int, m: int):
    """Every set of at most m lost rows, as (lost, the k rows kept)."""
    for r in range(m + 1):
        for lost in itertools.combinations(range(k + m), r):
            yield lost, [i for i in range(k + m) if i not in lost][:k]


@pytest.mark.parametrize("lost,kept", list(_patterns(2, 2)),
                         ids=lambda p: "-".join(map(str, p)) or "none")
def test_decode_rebuilds_every_pattern_at_2p2(lost, kept):
    full = _full(2, 2, 4099, len(lost) * 10 + sum(lost))
    got = reference.decode(full[kept], kept, 2, 2)
    assert np.array_equal(got, full)


@pytest.mark.parametrize("k,m", [(8, 4), (12, 4)])
def test_decode_rebuilds_a_seeded_sample_of_patterns(k, m):
    full = _full(k, m, 1031, k)
    pats = list(_patterns(k, m))
    for lost, kept in random.Random(k * 100 + m).sample(pats, 40):
        # any k of the survivors, not only the first k
        kept = sorted(random.Random(sum(lost)).sample(
            [i for i in range(k + m) if i not in lost], k))
        got = reference.decode(full[kept], kept, k, m)
        assert np.array_equal(got, full), (lost, kept)


def test_decode_of_the_data_rows_encodes_the_parity():
    full = _full(12, 4, 333, 7)
    got = reference.decode(full[:12], range(12), 12, 4)
    assert np.array_equal(got[12:], reference.encode_parity(full[:12], 4))


@pytest.mark.parametrize("present", [[0], [0, 0], [0, 4], [-1, 1]],
                         ids=["too-few", "twice", "out-of-range", "negative"])
def test_decode_refuses_a_pattern_that_is_not_k_distinct_rows(present):
    with pytest.raises(ValueError):
        reference.decode(np.zeros((len(present), 8), np.uint8), present,
                         2, 2)


# -- the loader's rules for down_drives ---------------------------------------

def _test_config() -> dict:
    with open(os.path.join(DOWN, "configs", TEST_CONFIG + ".json")) as f:
        return json.load(f)


def test_the_test_configuration_is_n4_ec2p2_with_one_drive_down():
    cfg = _test_config()
    assert manifest.check_config(cfg) is cfg
    n4 = manifest.load_data("configs", "n4-ec2p2")
    assert cfg["down_drives"] == [1]
    assert cfg["guarantees"]["shards_expected_on_healthy_drives"] == 3
    assert cfg["guarantees"]["write_quorum"] == 3
    for key in ("drives", "chips", "processes", "fixes"):
        assert cfg[key] == n4[key], key
    with open(os.path.join(DOWN, "workloads", TEST_CELL + ".json")) as f:
        w = json.load(f)
    assert (w["name"], w["config"], w["traffic"], w["chips"]) == (
        TEST_CELL, TEST_CONFIG, "warp-mixed-10m", 1)
    names = {c["name"] for c in manifest.load_manifest()["configs"]}
    assert TEST_CONFIG not in names


def test_every_accepted_configuration_keeps_the_healthy_path():
    for c in manifest.load_manifest()["configs"]:
        cfg = manifest.load_data("configs", c["name"])
        assert "down_drives" not in cfg
        assert manifest.check_config(copy.deepcopy(cfg)) == cfg


@pytest.mark.parametrize("down,expect", [
    ([1, 1], 3), ([4], 3), ([-1], 3), ([True], 3), (1, 3), ([], 4),
    ([0, 1], 2), ([1], 4), ([1], 2),
], ids=["twice", "past-the-end", "negative", "bool", "not-a-list",
        "empty", "under-write-quorum", "expects-healthy", "expects-fewer"])
def test_the_loader_refuses_a_broken_declaration(down, expect):
    cfg = _test_config()
    cfg["down_drives"] = down
    cfg["guarantees"]["shards_expected_on_healthy_drives"] = expect
    with pytest.raises(manifest.ManifestError):
        manifest.check_config(cfg)


def test_the_loader_allows_up_to_parity_shards_down():
    n16 = manifest.load_data("configs", "n16-ec12p4")
    for down in ([3], [0, 15], [2, 5, 9], [1, 4, 7, 10]):
        cfg = copy.deepcopy(n16)
        cfg["down_drives"] = down
        cfg["guarantees"]["shards_expected_on_healthy_drives"] = \
            16 - len(down)
        assert manifest.check_config(cfg) is cfg
    cfg["down_drives"] = [1, 4, 7, 10, 13]          # m + 1 = 5
    cfg["guarantees"]["shards_expected_on_healthy_drives"] = 11
    with pytest.raises(manifest.ManifestError):
        manifest.check_config(cfg)


# -- the oracle on layouts the program wrote ----------------------------------

SIZES = (3000, 2 * BS + 7, 3 * BS)        # inline; two blocks + a tail; three


def _body(n: int) -> bytes:
    return np.random.default_rng([9, n]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _layout(tmp_path, drives: int) -> list[str]:
    """``drives`` directories d00.. written by the program's object layer
    (host codec, default parity for the count), one object per size."""
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage.xl_storage import XLStorage
    dirs = [str(tmp_path / f"d{i:02d}") for i in range(drives)]
    for d in dirs:
        os.makedirs(d)
    layer = ErasureObjects([XLStorage(d) for d in dirs], block_size=BS,
                           backend="numpy")
    layer.make_bucket(BUCKET)
    for n in SIZES:
        layer.put_object(BUCKET, f"obj-{n}", _body(n))
    return dirs


def _verify(dirs, n: int, k: int, m: int, expect: int, down=()) -> dict:
    return oracle.verify_on_disk(dirs, BUCKET, f"obj-{n}", _body(n), k, m,
                                 expect, down)


def _blocks(n: int) -> int:
    return -(-n // BS)


@pytest.mark.parametrize("drives,k,m", [(4, 2, 2), (12, 8, 4)])
def test_oracle_on_a_healthy_set(tmp_path, drives, k, m):
    """What it returned before a drive could fail: every frame of every
    shard, every block once."""
    dirs = _layout(tmp_path, drives)
    for n in SIZES:
        assert _verify(dirs, n, k, m, k + m) == {
            "frames": (k + m) * _blocks(n), "blocks": _blocks(n)}


@pytest.mark.parametrize("drives,k,m,down", [
    (4, 2, 2, (1,)), (4, 2, 2, (0,)), (12, 8, 4, (2, 7)),
    (12, 8, 4, (0, 3, 6, 11))])
def test_oracle_passes_a_set_whose_drives_failed_after_the_writes(
        tmp_path, drives, k, m, down):
    dirs = _layout(tmp_path, drives)
    dep = Deployment({"drives": drives, "down_drives": list(down)},
                     str(tmp_path), False, True)
    assert dep.dirs == dirs and dep.fail_drives() == list(down)
    for i in down:
        assert os.path.isfile(dirs[i]) and os.path.getsize(dirs[i]) == 0
        assert os.path.isdir(dirs[i] + ".failed")
    for n in SIZES:
        got = _verify(dirs, n, k, m, drives - len(down), down)
        assert got == {"frames": (k + m - len(down)) * _blocks(n),
                       "blocks": _blocks(n)}
    # the failed drives named wrongly: the missing shards are not theirs
    other = tuple(sorted({(i + 1) % drives for i in down} - set(down)))[:1]
    if other:
        with pytest.raises(Failed, match="missing"):
            _verify(dirs, 3 * BS, k, m, drives - len(down),
                    other + down[1:])


def _shard_dir(dirs, n: int, index: int) -> str:
    return oracle.read_shards(dirs, BUCKET, f"obj-{n}")[index]["dir"]


def _part_file(d: str, n: int) -> str:
    obj = os.path.join(d, BUCKET, f"obj-{n}")
    sub = [e for e in os.listdir(obj) if e != "xl.meta"]
    assert len(sub) == 1
    return os.path.join(obj, sub[0], "part.1")


def test_oracle_fails_a_shard_deleted_from_a_live_drive(tmp_path):
    dirs = _layout(tmp_path, 4)
    shutil.rmtree(os.path.join(_shard_dir(dirs, 3 * BS, 0), BUCKET,
                               f"obj-{3 * BS}"))
    with pytest.raises(Failed, match="3/4 drives hold a shard"):
        _verify(dirs, 3 * BS, 2, 2, 4)
    # on a failed set the count still holds it: 2 of the 3 expected
    dep = Deployment({"drives": 4, "down_drives": [3]}, str(tmp_path),
                     False, True)
    dep.fail_drives()
    with pytest.raises(Failed, match="drives hold a shard"):
        _verify(dirs, 3 * BS, 2, 2, 3, (3,))


@pytest.mark.parametrize("down", [(), (2,)], ids=["healthy", "failed"])
def test_oracle_fails_a_flipped_payload_byte_by_the_host_hash(tmp_path,
                                                              down):
    dirs = _layout(tmp_path, 4)
    dep = Deployment({"drives": 4, "down_drives": list(down)},
                     str(tmp_path), False, True)
    dep.fail_drives()
    live = sorted(oracle.read_shards(dirs, BUCKET, f"obj-{2 * BS + 7}"))
    path = _part_file(_shard_dir(dirs, 2 * BS + 7, live[-1]), 2 * BS + 7)
    with open(path, "r+b") as f:
        f.seek(32 + 1000)
        byte = f.read(1)
        f.seek(32 + 1000)
        f.write(bytes([byte[0] ^ 0x40]))
    with pytest.raises(Failed, match="host HighwayHash rejects frame 1"):
        _verify(dirs, 2 * BS + 7, 2, 2, 4 - len(down), down)


@pytest.mark.parametrize("down,last", [((), False), ((), True),
                                       ((0,), False)],
                         ids=["parity-first", "parity-last", "failed-set"])
def test_oracle_fails_a_parity_row_of_valid_frames_over_wrong_bytes(
        tmp_path, down, last):
    """Frames the host hash accepts, over bytes that are not the code's:
    only the comparison with the plain reference's rebuild sees them."""
    from minio_tpu.hashing import highwayhash
    dirs = _layout(tmp_path, 4)
    dep = Deployment({"drives": 4, "down_drives": list(down)},
                     str(tmp_path), False, True)
    n = 2 * BS + 7
    held = oracle.read_shards(dirs, BUCKET, f"obj-{n}")
    parity = [i for i in (2, 3) if held[i]["dir"] not in
              [dirs[d] for d in down]]
    holder = held[parity[-1] if last else parity[0]]["dir"]
    dep.fail_drives()
    path = _part_file(holder, n)
    with open(path, "rb") as f:
        raw = np.frombuffer(f.read(), np.uint8).copy()
    ss = BS // 2
    for b in range(_blocks(n)):
        lo = b * (32 + ss) + 32
        hi = min(lo + ss, raw.size)
        raw[lo:hi] = np.random.default_rng(b).integers(0, 256, hi - lo,
                                                       dtype=np.uint8)
    assert highwayhash.hh256_fill(raw, ss)
    assert highwayhash.hh256_verify_framed(raw, ss) == 0
    with open(path, "wb") as f:
        f.write(raw.tobytes())
    with pytest.raises(Failed, match="differs from the plain reference"):
        _verify(dirs, n, 2, 2, 4 - len(down), down)


# -- the failed set rehearsed on the CPU --------------------------------------

def _checkout(tmp_path, patch: tuple[str, str, str] | None = None) -> str:
    """A copy of the checkout whose BENCHMARK.json also names the test
    configuration and its cell.  The program is linked, not copied, unless
    ``patch`` = (file under minio_tpu/, text, replacement) breaks it: then
    the copy's file has the text replaced once."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(manifest.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(manifest.ROOT, "native"), root / "native")
    if patch is None:
        os.symlink(os.path.join(manifest.ROOT, "minio_tpu"),
                   root / "minio_tpu")
    else:
        shutil.copytree(os.path.join(manifest.ROOT, "minio_tpu"),
                        root / "minio_tpu",
                        ignore=shutil.ignore_patterns("__pycache__"))
        path, text, replacement = patch
        with open(root / "minio_tpu" / path) as f:
            src = f.read()
        assert src.count(text) == 1, (path, text)
        with open(root / "minio_tpu" / path, "w") as f:
            f.write(src.replace(text, replacement))
    for kind, name in (("configs", TEST_CONFIG), ("workloads", TEST_CELL)):
        shutil.copy(os.path.join(DOWN, kind, name + ".json"),
                    root / "benchmarks" / kind / (name + ".json"))
    m = manifest.load_manifest()
    cfg = _test_config()
    m["configs"].append({
        "name": TEST_CONFIG, "source": cfg["source"],
        "file": f"benchmarks/configs/{TEST_CONFIG}.json", "reduced": [],
        "why": "test only: n4-ec2p2 with drive 1 failed after the preload"})
    with open(os.path.join(DOWN, "workloads", TEST_CELL + ".json")) as f:
        m["workloads"].append(json.load(f))
    manifest.validate(m)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(m, f, indent=1)
    return str(root)


def _rehearse(root: str, cell: str = TEST_CELL) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MT_FSYNC", None)
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", "2147499041", "--seconds", "6", "--trace", "0",
         "--rehearse"], cwd=root, env=env, capture_output=True, text=True,
        timeout=900)


def test_rehearse_with_one_drive_down(tmp_path):
    p = _rehearse(_checkout(tmp_path))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"ops_per_s", "setup_s"}
    fault = [x for x in lines if "fault: drives [1] failed" in x]
    assert len(fault) == 1, lines
    # GETs completed on the failed set, and the device codec rebuilt
    assert last["checks"]["matmul_dispatches"]["at_least"] == 1
    assert last["checks"]["matmul_dispatches"]["value"] >= 1
    assert last["checks"]["wrong_on_disk"] == {"value": 0, "at_most": 0}
    assert last["checks"]["wrong_drives_holding"] == {"value": 0,
                                                      "at_most": 0}


# (cell, file under minio_tpu/, text, replacement, what must say so)
FAULTS = {
    # the device rebuild hands back one wrong byte per lost shard: the
    # degraded GETs and the read-back see it
    "rebuild": (TEST_CELL, "ops/codec.py", "        return outs\n",
                "        for o in outs:\n            o[0] ^= 1\n"
                "        return outs\n",
                ("problem: GET ", "problem: read-back ")),
    # a PUT lands one wrong byte in its last parity shard on a healthy
    # set: the clients read the data shards and see nothing; the on-disk
    # oracle does
    "parity": ("n4.put-10m", "objectlayer/erasure_object.py",
               "            return self._codec_for(m).encode_framed(\n"
               "                data, self.bitrot_algo, out=out)\n",
               "            rows = self._codec_for(m).encode_framed(\n"
               "                data, self.bitrot_algo, out=out)\n"
               "            bad = bytearray(rows[-1])\n"
               "            bad[40] ^= 1\n"
               "            rows[-1] = bytes(bad)\n"
               "            return rows\n",
               ("problem: on-disk oracle: ",)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_rehearse_fails_an_answer_altered_where_it_is_produced(tmp_path,
                                                              fault):
    cell, path, text, replacement, said = FAULTS[fault]
    p = _rehearse(_checkout(tmp_path, (path, text, replacement)), cell)
    assert p.returncode != 0, p.stdout[-3000:]
    lines = p.stdout.strip().splitlines()
    assert not lines[-1].startswith("{")
    assert "rehearsal found problems" in lines[-1], lines[-1]
    assert any(x.split("] ", 1)[-1].startswith(said) for x in lines), \
        lines[-20:]
