"""Multipart uploads (cmd/erasure-multipart.go).

Uploads stage under the system volume at
``multipart/<sha256(bucket/object)>/<uploadID>/`` on every drive
(reference: .minio.sys/multipart, :36-44).  Each part is erasure-encoded
and bitrot-framed at PutObjectPart time (:342) — on TPU this is the same
single batched dispatch as whole-object PUT, so a 1 GiB multipart upload
streams through the device part by part.  CompleteMultipartUpload merges
the parts into the final version journal (:678) by renaming staged shard
files into the object's data dir and committing xl.meta with the part
table; the multipart ETag is md5(concat(part-md5s))-N.
"""

from __future__ import annotations

import hashlib
import uuid
from dataclasses import dataclass, field
from typing import Optional

from ..hashing import md5fast
from ..storage import errors as serrors
from ..storage.datatypes import (ChecksumInfo, ErasureInfo, FileInfo,
                                 ObjectPartInfo, now_ns)
from ..storage.xl_storage import SYS_DIR
from . import metadata as meta
from .interface import (InvalidPart, InvalidPartOrder, InvalidUploadID,
                        ObjectInfo, PutObjectOptions, WriteQuorumError)

MIN_PART_SIZE = 5 * 1024 * 1024     # S3 limit (last part exempt)
MAX_PARTS = 10_000                  # docs/minio-limits.md:28-33


@dataclass
class PartInfo:
    part_number: int
    etag: str
    size: int
    actual_size: int
    mod_time: int = 0


@dataclass
class MultipartInfo:
    bucket: str
    object_name: str
    upload_id: str
    user_defined: dict[str, str] = field(default_factory=dict)


class MultipartOps:
    """Mixin for ErasureObjects: the multipart side of the ObjectLayer."""

    def _mp_dir(self, bucket: str, object_name: str, upload_id: str) -> str:
        h = hashlib.sha256(f"{bucket}/{object_name}".encode()).hexdigest()
        return f"multipart/{h}/{upload_id}"

    def new_multipart_upload(self, bucket: str, object_name: str,
                             opts: Optional[PutObjectOptions] = None) -> str:
        opts = opts or PutObjectOptions()
        self._check_bucket(bucket)
        upload_id = uuid.uuid4().hex
        mp = self._mp_dir(bucket, object_name, upload_id)
        distribution = meta.hash_order(f"{bucket}/{object_name}",
                                       len(self.disks))
        k, m = self._geometry(opts.parity)
        fi = FileInfo(
            volume=bucket, name=object_name, version_id="",
            data_dir=str(uuid.uuid4()), mod_time=now_ns(),
            metadata={**opts.user_defined,
                      "__versioned": "1" if opts.versioned else "0",
                      "__bucket": bucket, "__object": object_name},
            erasure=ErasureInfo(
                data_blocks=k, parity_blocks=m,
                block_size=self.block_size, distribution=distribution))

        def init_one(idx_disk):
            idx, disk = idx_disk
            dfi = FileInfo(**{**fi.__dict__})
            dfi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
            dfi.erasure.index = idx + 1
            disk.write_metadata(SYS_DIR, mp, dfi)

        shuffled = meta.shuffle_disks(self.disks, distribution)
        _, errs = self._fanout_indexed(init_one, shuffled)
        try:
            meta.reduce_errs(errs, self._write_quorum(fi), WriteQuorumError)
        except serrors.StorageError as e:
            raise WriteQuorumError(str(e)) from e
        return upload_id

    def _mp_fileinfo(self, bucket: str, object_name: str,
                     upload_id: str) -> tuple[FileInfo, list]:
        mp = self._mp_dir(bucket, object_name, upload_id)
        fis, errs = self._fanout(lambda d: d.read_version(SYS_DIR, mp))
        ok = [fi for fi in fis if fi is not None]
        if len(ok) < max(1, len(self.disks) // 2):
            raise InvalidUploadID(upload_id)
        fi = meta.find_file_info_in_quorum(fis, max(1, len(self.disks) // 2))
        return fi, fis

    def put_object_part(self, bucket: str, object_name: str, upload_id: str,
                        part_number: int, data) -> PartInfo:
        """Erasure-encode one part (PutObjectPart,
        cmd/erasure-multipart.go:342).  ``data`` is bytes or a file-like
        reader; large parts stream through the block-batched pipeline so
        memory stays O(batch) — a 5 GiB part never materializes.  Parts
        ride the same per-drive writer plane as streaming PUT (part
        md5 + encode + drive appends overlap); bytes bodies feed the
        loop zero-copy memoryview slices."""
        if not 1 <= part_number <= MAX_PARTS:
            raise InvalidPart(f"part number {part_number}")
        self._check_bucket(bucket)
        fi, _ = self._mp_fileinfo(bucket, object_name, upload_id)
        mp = self._mp_dir(bucket, object_name, upload_id)
        from .erasure_object import _read_full
        batch = self._stream_batch_size()
        if hasattr(data, "read"):
            def _chunks(reader=data):
                first = True
                while True:
                    c = _read_full(reader, batch)
                    if c or first:     # empty body still stages a part
                        yield c
                    first = False
                    if len(c) < batch:
                        return
            chunks = _chunks()
        else:
            body = data if isinstance(data, bytes) else bytes(data)
            mv = memoryview(body)
            chunks = (mv[o:o + batch]
                      for o in range(0, max(1, len(mv)), batch))
        shuffled = meta.shuffle_disks(self.disks, fi.erasure.distribution)
        wq = self._write_quorum(fi)
        # stage under a unique name, promote atomically at the end: a
        # retried or concurrent upload of the same part number must never
        # truncate a part that already verified (the reference writes
        # whole part files via tmp+rename, cmd/erasure-multipart.go:342)
        staging = f"part.{part_number}.in.{uuid.uuid4().hex[:8]}"
        if self._pipeline_on():
            return self._put_part_pipelined(
                bucket, object_name, fi, mp, staging, part_number,
                chunks, shuffled, wq)
        return self._put_part_serial(
            bucket, object_name, fi, mp, staging, part_number, chunks,
            shuffled, wq)

    def _put_part_serial(self, bucket, object_name, fi, mp, staging,
                         part_number, chunks, shuffled, wq) -> PartInfo:
        n = len(self.disks)
        errs: list[Exception | None] = [None] * n
        started = [False] * n
        md5 = md5fast.md5()
        size = 0
        try:
            for chunk in chunks:
                md5.update(chunk)
                size += len(chunk)
                # the upload's persisted geometry wins: a storage-class
                # parity chosen at initiate applies to every part
                framed = self._encode_and_frame(
                    chunk, fi.erasure.parity_blocks, fi)

                def write_batch(idx_disk):
                    idx, disk = idx_disk
                    if disk is None or errs[idx] is not None:
                        return
                    if not started[idx]:
                        started[idx] = True
                        disk.create_file(SYS_DIR, f"{mp}/{staging}",
                                         framed[idx])
                    else:
                        disk.append_file(SYS_DIR, f"{mp}/{staging}",
                                         framed[idx])

                _, werrs = self._fanout_indexed(write_batch, shuffled)
                for i, e in enumerate(werrs):
                    if e is not None and errs[i] is None:
                        errs[i] = e
                alive = sum(1 for i, d in enumerate(shuffled)
                            if d is not None and errs[i] is None)
                if alive < wq:
                    raise WriteQuorumError(
                        f"{alive} of {n} drives writable, need {wq}")
            etag = md5.hexdigest()

            def promote(idx_disk):
                idx, disk = idx_disk
                if disk is None:
                    raise serrors.DiskNotFound("offline")
                if errs[idx] is not None:
                    raise errs[idx]
                # atomic promote, then the sidecar complete() verifies with
                disk.rename_file(SYS_DIR, f"{mp}/{staging}",
                                 SYS_DIR, f"{mp}/part.{part_number}")
                disk.write_all(SYS_DIR, f"{mp}/part.{part_number}.meta",
                               f"{etag}:{size}".encode())

            _, perrs = self._fanout_indexed(promote, shuffled)
            try:
                meta.reduce_errs(perrs, wq, WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            return PartInfo(part_number, etag, size, size, now_ns())
        finally:
            # drop any staging file that wasn't promoted (stream abort,
            # failed drive, lost quorum): a later retry must never see it
            def cleanup(idx_disk):
                idx, disk = idx_disk
                if disk is None or not started[idx]:
                    return
                try:
                    disk.delete(SYS_DIR, f"{mp}/{staging}")
                except Exception:  # noqa: BLE001 — already promoted/gone
                    pass

            self._fanout_indexed(cleanup, shuffled)

    def _put_part_pipelined(self, bucket, object_name, fi, mp, staging,
                            part_number, chunks, shuffled, wq) -> PartInfo:
        """Part upload on the per-drive writer plane: the shared stage
        driver (_pump_put_pipeline) overlaps chained md5, encode, and
        per-drive appends exactly like streaming PUT; the staged-name
        promote and cleanup contracts match the serial path bit for
        bit."""
        n = len(self.disks)
        m = fi.erasure.parity_blocks
        sw = self._write_plane.stream(shuffled)
        started = [False] * n
        # the lane-aware digest: concurrent parts' _md5_link chains
        # coalesce in the native multi-lane scheduler (config 2's 8+4
        # multipart uploads hash their parts side by side in one call)
        md5 = md5fast.md5()
        stats = {"md5_s": 0.0, "encode_s": 0.0}
        try:
            def write_batch_for(framed):
                def write_batch(idx, disk):
                    if not started[idx]:
                        started[idx] = True
                        disk.create_file(SYS_DIR, f"{mp}/{staging}",
                                         framed[idx])
                    else:
                        disk.append_file(SYS_DIR, f"{mp}/{staging}",
                                         framed[idx])
                return write_batch

            size, _ = self._pump_put_pipeline(
                chunks, sw, m, fi, md5, stats, write_batch_for, wq)
            etag = md5.hexdigest()
            sw.drain()
            alive = sw.alive()
            if alive < wq:
                raise WriteQuorumError(
                    f"{alive} of {n} drives writable, need {wq}")

            def promote(idx, disk):
                # atomic promote, then the sidecar complete() verifies
                # with; per-drive FIFO guarantees every append landed
                disk.rename_file(SYS_DIR, f"{mp}/{staging}",
                                 SYS_DIR, f"{mp}/part.{part_number}")
                disk.write_all(SYS_DIR, f"{mp}/part.{part_number}.meta",
                               f"{etag}:{size}".encode())

            sw.submit_batch(promote)
            sw.drain()
            try:
                meta.reduce_errs(list(sw.errs), wq, WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            return PartInfo(part_number, etag, size, size, now_ns())
        finally:
            sw.abort()
            sw.drain(timeout=10.0)   # settle queues before cleanup

            def cleanup(idx_disk):
                idx, disk = idx_disk
                if disk is None or not started[idx]:
                    return
                # settled drives delete inline (still on the parallel
                # fan-out); a drive hung past the drain timeout defers
                # to op settlement so its resumed append cannot
                # resurrect the staging file after this delete
                sw.when_drive_idle(
                    idx,
                    lambda d=disk: d.delete(SYS_DIR, f"{mp}/{staging}"))

            self._fanout_indexed(cleanup, shuffled)

    def get_multipart_info(self, bucket: str, object_name: str,
                           upload_id: str) -> MultipartInfo:
        """Upload metadata (cmd/erasure-multipart.go GetMultipartInfo) —
        the SSE path needs the sealed object key stored at initiation."""
        self._check_bucket(bucket)
        fi, _ = self._mp_fileinfo(bucket, object_name, upload_id)
        md = {k: v for k, v in fi.metadata.items()
              if not k.startswith("__")}
        return MultipartInfo(bucket, object_name, upload_id, md)

    def list_object_parts(self, bucket: str, object_name: str,
                          upload_id: str) -> list[PartInfo]:
        self._check_bucket(bucket)
        fi, _ = self._mp_fileinfo(bucket, object_name, upload_id)
        mp = self._mp_dir(bucket, object_name, upload_id)
        # merge sidecars across ALL drives: a part that met write quorum may
        # be absent from any single drive (transient per-drive failure)
        parts: dict[int, PartInfo] = {}
        found_any = False
        for disk in self.disks:
            if disk is None:
                continue
            try:
                names = disk.list_dir(SYS_DIR, mp)
                found_any = True
            except serrors.StorageError:
                continue
            for n in names:
                if not (n.startswith("part.") and n.endswith(".meta")):
                    continue
                num = int(n[5:-5])
                if num in parts:
                    continue
                try:
                    etag, size = disk.read_all(
                        SYS_DIR, f"{mp}/{n}").decode().split(":")
                except (serrors.StorageError, ValueError):
                    continue
                parts[num] = PartInfo(num, etag, int(size), int(size))
        if not found_any:
            raise InvalidUploadID(upload_id)
        return sorted(parts.values(), key=lambda p: p.part_number)

    def abort_multipart_upload(self, bucket: str, object_name: str,
                               upload_id: str) -> None:
        self._check_bucket(bucket)
        self._mp_fileinfo(bucket, object_name, upload_id)  # validates
        mp = self._mp_dir(bucket, object_name, upload_id)
        self._fanout(lambda d: d.delete(SYS_DIR, mp, recursive=True))

    def list_multipart_uploads(self, bucket: str,
                               prefix: str = "") -> list[MultipartInfo]:
        self._check_bucket(bucket)
        # merge across ALL drives: an upload that met write quorum may be
        # missing from any single drive
        out: dict[str, MultipartInfo] = {}
        for disk in self.disks:
            if disk is None:
                continue
            try:
                hashes = disk.list_dir(SYS_DIR, "multipart")
            except serrors.StorageError:
                continue
            for h in hashes:
                try:
                    uploads = disk.list_dir(SYS_DIR,
                                            f"multipart/{h.strip('/')}")
                except serrors.StorageError:
                    continue
                for u in uploads:
                    uid = u.strip("/")
                    if uid in out:
                        continue
                    try:
                        fi = disk.read_version(
                            SYS_DIR, f"multipart/{h.strip('/')}/{uid}")
                    except serrors.StorageError:
                        continue
                    obj = fi.metadata.get("__object", "")
                    if obj.startswith(prefix) and \
                            fi.metadata.get("__bucket") == bucket:
                        md = {k: v for k, v in fi.metadata.items()
                              if not k.startswith("__")}
                        out[uid] = MultipartInfo(bucket, obj, uid, md)
        return sorted(out.values(), key=lambda m: m.object_name)

    def complete_multipart_upload(self, bucket: str, object_name: str,
                                  upload_id: str,
                                  parts: list[tuple[int, str]],
                                  opts: Optional[PutObjectOptions] = None
                                  ) -> ObjectInfo:
        """parts: [(part_number, etag)] in client order; must be ascending
        (CompleteMultipartUpload, cmd/erasure-multipart.go:678).  ``opts``
        lets a rebalance/decommission move re-commit a multipart version
        under its original version_id/mod_time; same part bytes give the
        same part md5s, so the merged ETag is already bit-identical."""
        self._check_bucket(bucket)
        fi, _ = self._mp_fileinfo(bucket, object_name, upload_id)
        mp = self._mp_dir(bucket, object_name, upload_id)
        if not parts:
            raise InvalidPart("no parts specified")
        if [p[0] for p in parts] != sorted({p[0] for p in parts}):
            raise InvalidPartOrder("parts not in ascending order")
        uploaded = {p.part_number: p
                    for p in self.list_object_parts(bucket, object_name,
                                                    upload_id)}
        part_infos: list[ObjectPartInfo] = []
        md5s = b""
        total = 0
        for i, (num, etag) in enumerate(parts):
            got = uploaded.get(num)
            if got is None or got.etag != etag.strip('"'):
                raise InvalidPart(f"part {num}")
            if got.size < MIN_PART_SIZE and i != len(parts) - 1 \
                    and self.enforce_min_part_size:
                raise InvalidPart(f"part {num} too small")
            part_infos.append(ObjectPartInfo(num, got.size, got.size,
                                             got.etag, now_ns()))
            md5s += bytes.fromhex(got.etag)
            total += got.size
        etag = hashlib.md5(md5s).hexdigest() + f"-{len(parts)}"

        versioned = fi.metadata.pop("__versioned", "0") == "1"
        if opts is not None and opts.versioned:
            versioned = True
        version_id = str(uuid.uuid4()) if versioned else ""
        mod_time = now_ns()
        if opts is not None:
            version_id = opts.version_id or version_id
            mod_time = opts.mod_time or mod_time
        fi.volume, fi.name = bucket, object_name
        fi.version_id = version_id
        fi.mod_time = mod_time
        fi.size = total
        fi.parts = part_infos
        fi.metadata = {k: v for k, v in fi.metadata.items()
                       if not k.startswith("__")}
        fi.metadata["etag"] = etag
        fi.erasure.checksums = [ChecksumInfo(p.number, self.bitrot_algo)
                                for p in part_infos]
        shuffled = meta.shuffle_disks(self.disks, fi.erasure.distribution)

        def commit_one(idx_disk):
            idx, disk = idx_disk
            dfi = FileInfo(**{**fi.__dict__})
            dfi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
            dfi.erasure.index = idx + 1
            tmp = disk.tmp_dir()
            try:
                for p in part_infos:
                    disk.rename_file(SYS_DIR, f"{mp}/part.{p.number}",
                                     SYS_DIR, f"{tmp}/part.{p.number}")
                disk.rename_data(SYS_DIR, tmp, dfi, bucket, object_name)
            finally:
                disk.clean_tmp(tmp)
            disk.delete(SYS_DIR, mp, recursive=True)

        # the commit mutates the object's version set across drives:
        # same ns write lock as PUT/DELETE (the reference's
        # CompleteMultipartUpload takes the nsLock on the object), so a
        # racing GET can never observe a half-renamed version set
        lk = self.ns_lock.new_lock(bucket, object_name)
        lk.lock(write=True)
        try:
            _, errs = self._fanout_indexed(commit_one, shuffled)
            try:
                meta.reduce_errs(errs, self._write_quorum(fi),
                                 WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            # hot-read fence INSIDE the locked commit section, like
            # every other write path (invalidate-before-visible)
            self._hot_invalidate(bucket, object_name)
        finally:
            lk.unlock()
        fi.is_latest = True
        self.metacache.invalidate(bucket)
        return self._to_object_info(fi)
