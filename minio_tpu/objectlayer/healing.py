"""Object healing (cmd/erasure-healing.go:233 healObject,
cmd/erasure-lowlevel-heal.go Erasure.Heal).

Classify each drive for a given object version as ok / outdated / offline
(listOnlineDisks + disksWithAllParts analog, cmd/erasure-healing-common.go),
then rebuild the missing shards: read the k healthiest shard files, run the
decode matmul on device for the *wanted* shard indices (one batched dispatch
covers every stripe), re-frame with bitrot, and commit to the stale drives
with tmp+rename_data.  Dangling objects (fewer than k shards anywhere) are
purged, as in purgeObjectDangling (cmd/erasure-healing.go:692).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..hashing import bitrot
from ..storage import errors as serrors
from ..storage.datatypes import ErasureInfo, FileInfo
from ..storage.xl_storage import SYS_DIR
from . import metadata as meta
from .interface import ObjectNotFound
from .erasure_object import ErasureObjects


@dataclass
class HealResult:
    """mirror of madmin.HealResultItem essentials."""
    bucket: str
    object_name: str
    version_id: str = ""
    before_ok: int = 0
    after_ok: int = 0
    healed_disks: list[str] = field(default_factory=list)
    dangling_purged: bool = False


class DiskState:
    OK = "ok"
    OFFLINE = "offline"
    MISSING = "missing"          # no metadata / no parts
    OUTDATED = "outdated"        # stale version
    CORRUPT = "corrupt"          # bitrot / bad part sizes


def classify_disks(er: ErasureObjects, bucket: str, object_name: str,
                   fi: FileInfo, fis: list[FileInfo | None],
                   errs: list[Exception | None],
                   deep: bool = False) -> list[str]:
    """Per-disk state for the quorum version ``fi``
    (listOnlineDisks/disksWithAllParts semantics)."""
    states = []
    shuffled = meta.shuffle_disks(er.disks, fi.erasure.distribution)
    s_fis = meta.shuffle_parts_metadata(fis, fi.erasure.distribution)
    s_errs = meta.shuffle_parts_metadata(errs, fi.erasure.distribution)
    for disk, dfi, derr in zip(shuffled, s_fis, s_errs):
        if disk is None or isinstance(derr, serrors.DiskNotFound):
            states.append(DiskState.OFFLINE)
            continue
        if isinstance(derr, (serrors.FileNotFound,
                             serrors.FileVersionNotFound,
                             serrors.VolumeNotFound)):
            states.append(DiskState.MISSING)
            continue
        if derr is not None:
            states.append(DiskState.CORRUPT)
            continue
        if dfi is None or dfi.mod_time != fi.mod_time:
            states.append(DiskState.OUTDATED)
            continue
        if dfi.inline_data is not None:
            states.append(DiskState.OK)
            continue
        try:
            if deep:
                disk.verify_file(bucket, object_name, dfi)
            else:
                disk.check_parts(bucket, object_name, dfi)
            states.append(DiskState.OK)
        except serrors.StorageError:
            states.append(DiskState.CORRUPT)
    return states


def heal_object(er: ErasureObjects, bucket: str, object_name: str,
                version_id: Optional[str] = None, deep: bool = False,
                dry_run: bool = False, remove_dangling: bool = False
                ) -> HealResult:
    """HealObject for one version (cmd/erasure-healing.go:803,233)."""
    fis, errs = er._fanout(
        lambda d: d.read_version(bucket, object_name, version_id))
    ok_reads = [fi for fi in fis if fi is not None]
    if not ok_reads:
        raise ObjectNotFound(f"{bucket}/{object_name}")
    try:
        fi = meta.find_file_info_in_quorum(fis, max(1, len(er.disks) // 2))
    except meta.ReadQuorumError:
        # metadata below quorum: the object can never be served again —
        # dangling (purgeObjectDangling, cmd/erasure-healing.go:692)
        fi = ok_reads[0]
        res = HealResult(bucket, object_name, fi.version_id)
        res.before_ok = len(ok_reads)
        if remove_dangling and not dry_run:
            er._fanout(lambda d: d.delete_version(bucket, object_name, fi))
            res.dangling_purged = True
        res.after_ok = res.before_ok
        return res
    k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
    res = HealResult(bucket, object_name, fi.version_id)

    states = classify_disks(er, bucket, object_name, fi, fis, errs, deep)
    res.before_ok = states.count(DiskState.OK)
    healable = [i for i, s in enumerate(states)
                if s in (DiskState.MISSING, DiskState.OUTDATED,
                         DiskState.CORRUPT)]

    if res.before_ok < k:
        # dangling: not enough shards anywhere to ever reconstruct
        if remove_dangling and not dry_run:
            er._fanout(lambda d: d.delete_version(bucket, object_name, fi))
            res.dangling_purged = True
        res.after_ok = res.before_ok
        return res

    if not healable or dry_run:
        res.after_ok = res.before_ok
        return res

    shuffled = meta.shuffle_disks(er.disks, fi.erasure.distribution)
    s_fis = meta.shuffle_parts_metadata(fis, fi.erasure.distribution)
    ssize = fi.erasure.shard_size()

    # heal the bucket volume first (healBucket, cmd/erasure-healing.go:56)
    for i in healable:
        try:
            shuffled[i].stat_vol(bucket)
        except serrors.VolumeNotFound:
            try:
                shuffled[i].make_vol(bucket)
            except serrors.StorageError:
                pass
        except serrors.StorageError:
            pass

    # delete markers / zero-byte objects: metadata-only heal
    if fi.deleted or fi.size == 0 or not fi.parts:
        for i in healable:
            dfi = _disk_fileinfo(fi, i)
            shuffled[i].write_metadata(bucket, object_name, dfi)
            res.healed_disks.append(shuffled[i].endpoint())
        res.after_ok = res.before_ok + len(healable)
        return res

    ok_idx = [i for i, s in enumerate(states) if s == DiskState.OK]
    inline = any(f is not None and f.inline_data is not None
                 for f in s_fis)
    # packed small objects live in per-drive segment files; the healed
    # shard re-packs on the TARGET drive (its own segment, its own
    # extent) so the object stays uniformly packed across the set
    packed = any(f is not None and getattr(f, "seg", None) is not None
                 for f in s_fis)

    # stage every part into ONE tmp dir per drive as it is rebuilt,
    # commit with a single rename_data per drive at the end:
    # rename_data REPLACES the object's data dir, so a per-part commit
    # would clobber previously healed parts and leave a multipart
    # object permanently CORRUPT on the target drive (only its last
    # part present).  Staging goes straight to the drive, so heal
    # memory stays O(one part's shards), not O(all parts).
    staged: dict[int, str] = {}          # shard idx -> tmp dir
    stage_errs: dict[int, Exception] = {}
    try:
        for part in fi.parts:
            sfsize = fi.erasure.shard_file_size(part.size)
            # read k healthy shard files (verified)
            shards: dict[int, np.ndarray] = {}
            for i in ok_idx:
                if len(shards) == k:
                    break
                try:
                    dfi = s_fis[i]
                    if dfi is not None and dfi.inline_data is not None:
                        framed = dfi.inline_data
                    elif dfi is not None and \
                            getattr(dfi, "seg", None) is not None:
                        framed = shuffled[i].read_segment(
                            dfi.seg["sid"], dfi.seg["off"], dfi.seg["len"])
                    else:
                        framed = shuffled[i].read_all(
                            bucket,
                            f"{object_name}/{fi.data_dir}"
                            f"/part.{part.number}")
                    r = bitrot.StreamingBitrotReader(framed, ssize,
                                                     er.bitrot_algo)
                    shards[i] = np.frombuffer(r.read_at(0, sfsize),
                                              dtype=np.uint8)
                except (serrors.StorageError, bitrot.BitrotError):
                    continue
            if len(shards) < k:
                res.after_ok = res.before_ok
                return res
            present = sorted(shards)[:k]
            wanted = healable
            # matrix for the OBJECT's geometry: storage-class parity
            # may differ from the layer default
            rebuilt = er._codec_for(
                fi.erasure.parity_blocks).reconstruct_files(
                    [shards[i] for i in present], present, wanted,
                    part.size, block_size=fi.erasure.block_size)
            for j, i in enumerate(wanted):
                if i in stage_errs:
                    continue            # drive already failed staging
                framed = bitrot.streaming_encode(rebuilt[j].tobytes(),
                                                 ssize, er.bitrot_algo)
                disk = shuffled[i]
                if inline or fi.size <= er.inline_threshold:
                    dfi = _disk_fileinfo(fi, i)
                    dfi.inline_data = framed
                    dfi.data_dir = ""
                    disk.write_metadata(bucket, object_name, dfi)
                    if disk.endpoint() not in res.healed_disks:
                        res.healed_disks.append(disk.endpoint())
                    continue
                if packed:
                    dfi = _disk_fileinfo(fi, i)
                    dfi.data_dir = ""
                    disk.write_packed(bucket, object_name, dfi, framed)
                    if disk.endpoint() not in res.healed_disks:
                        res.healed_disks.append(disk.endpoint())
                    continue
                try:
                    tmp = staged.get(i)
                    if tmp is None:
                        tmp = staged[i] = disk.tmp_dir()
                    disk.create_file(SYS_DIR,
                                     f"{tmp}/part.{part.number}", framed)
                except (serrors.StorageError, OSError) as e:
                    # one drive failing to stage must not sink the
                    # others' heal; its error surfaces after commit
                    stage_errs[i] = e
        writes = [(shuffled[i], _disk_fileinfo(fi, i), staged[i])
                  for i in healable
                  if i in staged and i not in stage_errs]
        _commit_healed_shards(er, writes, bucket, object_name, res)
        if stage_errs:
            raise next(iter(stage_errs.values()))
    finally:
        for i, tmp in staged.items():
            try:
                shuffled[i].clean_tmp(tmp)
            except Exception:  # noqa: BLE001 — cleanup best-effort
                pass
    res.after_ok = res.before_ok + len(healable)
    return res


def _commit_healed_shards(er: ErasureObjects, writes: list,
                          bucket: str, object_name: str, res) -> None:
    """Commit fully-staged shard tmp dirs on the stale drives: ONE
    rename_data per drive swaps its data dir atomically (the parts
    were already streamed into the tmp dir as they were rebuilt).
    Rides the shared per-drive writer plane when the pipeline is on,
    so remote drives' commit RPCs overlap; falls back to the serial
    loop otherwise.  The first failure aborts the heal (as the serial
    loop always did) — but only after every drive's commit settled,
    and drives that DID succeed are still recorded as healed.
    ``writes`` rows are (disk, dfi, tmp_dir)."""
    if not writes:
        return

    def heal_one(disk, dfi, tmp) -> None:
        disk.rename_data(SYS_DIR, tmp, dfi, bucket, object_name)

    if er._pipeline_on() and len(writes) > 1:
        sw = er._write_plane.stream([d for d, _, _ in writes])
        for pos, (disk, dfi, tmp) in enumerate(writes):
            # the plane hands fn its (idx, disk); the heal write is
            # already bound to ITS target drive, so ignore them
            sw.submit(pos, lambda *_, d=disk, i=dfi, t=tmp:
                      heal_one(d, i, t))
        sw.drain()
        first_err = None
        for pos, (disk, _, _) in enumerate(writes):
            if sw.errs[pos] is None:
                if disk.endpoint() not in res.healed_disks:
                    res.healed_disks.append(disk.endpoint())
            elif first_err is None:
                first_err = sw.errs[pos]
        if first_err is not None:
            raise first_err
        return
    for disk, dfi, tmp in writes:
        heal_one(disk, dfi, tmp)
        if disk.endpoint() not in res.healed_disks:
            res.healed_disks.append(disk.endpoint())


def _disk_fileinfo(fi: FileInfo, shard_idx: int) -> FileInfo:
    dfi = FileInfo(**{**fi.__dict__})
    dfi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
    dfi.erasure.index = shard_idx + 1
    dfi.inline_data = None
    # seg extents are per-drive: the quorum fi's extent points into the
    # SOURCE drive's segment file; the target re-packs (write_packed
    # assigns its own extent) or stages regular part files
    dfi.seg = None
    return dfi
