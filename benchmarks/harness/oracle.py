"""The on-disk oracle: what the drives hold, checked by code that never
touches the device path (a copy of ``chip_smoke.py``'s ``read_shards`` /
``verify_on_disk``).  Frame digests are checked with the program's HOST
HighwayHash (native C, ``minio_tpu/hashing``); every block is rebuilt from
k of the shards present by the benchmark's own plain reference
(``reference.py``), and what it rebuilds is compared with the body and
with every shard present.  On a set with failed drives the shards they
held are missing, and only those.
"""

from __future__ import annotations

import os

import numpy as np

from . import reference
from .deploy import check


def host_hash_ready() -> None:
    from minio_tpu.hashing import highwayhash
    check(highwayhash._get_lib() is not None,
          "host HighwayHash is the pure-Python fallback (no C compiler?)")


def read_shards(dirs: list[str], bucket: str, key: str) -> dict:
    """shard index -> {"dir", "parts": {n: framed bytes}, "fi"} for every
    drive that holds the object, straight from the drive directories."""
    from minio_tpu.storage.xl_meta import XLMeta
    out = {}
    for d in dirs:
        mp = os.path.join(d, bucket, key, "xl.meta")
        if not os.path.exists(mp):
            continue
        with open(mp, "rb") as f:
            fi = XLMeta.load(f.read()).to_fileinfo(bucket, key)
        parts = {}
        for p in fi.parts:
            if fi.inline_data is not None:
                parts[p.number] = bytes(fi.inline_data)
            elif fi.seg:
                seg = os.path.join(d, ".mt.sys", "seg",
                                   f"seg.{fi.seg['sid']:08x}.dat")
                with open(seg, "rb") as f:
                    f.seek(fi.seg["off"])
                    parts[p.number] = f.read(fi.seg["len"])
            else:
                with open(os.path.join(d, bucket, key, fi.data_dir,
                                       f"part.{p.number}"), "rb") as f:
                    parts[p.number] = f.read()
        out[fi.erasure.index - 1] = {"dir": d, "parts": parts, "fi": fi}
    return out


def verify_on_disk(dirs: list[str], bucket: str, key: str, body: bytes,
                   k: int, m: int, expect_shards: int,
                   down: tuple = ()) -> dict:
    """A shard on every drive the configuration promises, and none where
    the object's distribution put one on a failed drive (``down``: indices
    into ``dirs``); every present frame digest against the host
    HighwayHash; every block rebuilt from its first k present rows by the
    plain reference, the rebuilt data rows against the body and every
    present row against its rebuilt row (with all k+m present: the parity
    recomputed from the data shards)."""
    from minio_tpu.hashing import highwayhash
    shards = read_shards(dirs, bucket, key)
    check(len(shards) == expect_shards,
          f"{key}: {len(shards)}/{expect_shards} drives hold a shard")
    present = sorted(shards)
    fi = shards[present[0]]["fi"]
    ec = fi.erasure
    check((ec.data_blocks, ec.parity_blocks) == (k, m),
          f"{key}: geometry {ec.data_blocks}+{ec.parity_blocks}")
    lost = sorted(ec.distribution[d] - 1 for d in down)
    missing = sorted(set(range(k + m)) - set(present))
    check(missing == lost, f"{key}: shards {missing} are missing; the "
          f"failed drives {list(down)} held shards {lost}")
    use = present[:k]
    bs = ec.block_size
    ss = reference.ceil_div(bs, k)
    frames = blocks = off = 0
    for part in fi.parts:
        nfull, tail = divmod(part.size, bs)
        tail_ss = reference.ceil_div(tail, k)
        want_len = nfull * (32 + ss) + ((32 + tail_ss) if tail else 0)
        rows = []
        for i in present:
            raw = np.frombuffer(shards[i]["parts"][part.number], np.uint8)
            check(raw.size == want_len,
                  f"{key} part {part.number} shard {i}: {raw.size} bytes "
                  f"on disk, {want_len} expected")
            bad = highwayhash.hh256_verify_framed(raw, ss)
            check(bad == 0, f"{key} part {part.number} shard {i}: host "
                  f"HighwayHash rejects frame {bad}")
            rows.append(raw)
            frames += nfull + (1 if tail else 0)
        framed = np.stack(rows)
        pbody = np.frombuffer(body, np.uint8)[off:off + part.size]
        for b in range(nfull + (1 if tail else 0)):
            n = ss if b < nfull else tail_ss
            base = b * (32 + ss) + 32
            stripe = framed[:, base:base + n]
            full = reference.decode(np.ascontiguousarray(stripe[:k]), use,
                                    k, m)
            for j, i in enumerate(present):
                check(np.array_equal(full[i], stripe[j]),
                      f"{key} part {part.number} block {b}: shard {i} on "
                      f"disk differs from the plain reference's rebuild "
                      f"from shards {use}")
            blen = bs if b < nfull else tail
            check(np.array_equal(full[:k].reshape(-1)[:blen],
                                 pbody[b * bs:b * bs + blen]),
                  f"{key} part {part.number} block {b}: the data shards "
                  f"rebuilt from shards {use} differ from the body")
            blocks += 1
        off += part.size
    check(off == len(body), f"{key}: parts cover {off} of {len(body)}")
    return {"frames": frames, "blocks": blocks}
