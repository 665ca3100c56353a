"""Remote drive access over internode RPC
(cmd/storage-rest-{client,server}.go).

Every StorageAPI method of a local drive is exported as an RPC method; the
client side is a full StorageAPI so erasure sets treat remote drives
exactly like local ones.  Errors are re-raised as their typed storage
exceptions so quorum reduction works unchanged across the node boundary.
"""

from __future__ import annotations

import time
from typing import Iterable

import msgpack

from ..admin.metrics import GLOBAL as _metrics
from ..admin.metrics import KERNEL_BUCKETS
from ..obs import trace as _trace
from ..parallel.rpc import (STREAM, RPCClient, RPCError, RPCServer,
                            StreamBody)
from . import errors as serrors
from .api import DiskInfo, StorageAPI, VolInfo
from .datatypes import FileInfo
from .xl_storage import XLStorage

_ERR_TYPES = {cls.__name__: cls for cls in [
    serrors.DiskNotFound, serrors.UnformattedDisk, serrors.CorruptedFormat,
    serrors.DiskFull, serrors.VolumeNotFound, serrors.VolumeExists,
    serrors.VolumeNotEmpty, serrors.FileNotFound,
    serrors.FileVersionNotFound, serrors.FileNameTooLong,
    serrors.FileAccessDenied, serrors.FileCorrupt, serrors.IsNotRegular,
    serrors.PathNotEmpty, serrors.DiskAccessDenied, serrors.FaultyDisk,
    serrors.MethodNotAllowed,
]}


def register_storage_service(rpc: RPCServer,
                             drives: dict[str, XLStorage]) -> None:
    """Export local drives (keyed by drive id/path) on a node's RPC server
    (storage-rest-server.go handler table)."""

    def drive(drive_id: str) -> XLStorage:
        d = drives.get(drive_id)
        if d is None:
            raise serrors.DiskNotFound(drive_id)
        return d

    methods = {
        "disk_info": lambda drive_id: vars(drive(drive_id).disk_info()),
        "make_vol": lambda drive_id, volume:
            drive(drive_id).make_vol(volume),
        "list_vols": lambda drive_id: [
            {"name": v.name, "created": v.created}
            for v in drive(drive_id).list_vols()],
        "stat_vol": lambda drive_id, volume:
            (lambda v: {"name": v.name, "created": v.created})(
                drive(drive_id).stat_vol(volume)),
        "delete_vol": lambda drive_id, volume, force:
            drive(drive_id).delete_vol(volume, force),
        "list_dir": lambda drive_id, volume, dir_path, count:
            drive(drive_id).list_dir(volume, dir_path, count),
        "read_all": lambda drive_id, volume, path:
            drive(drive_id).read_all(volume, path),
        "write_all": lambda drive_id, volume, path, data:
            drive(drive_id).write_all(volume, path, data),
        "create_file": lambda drive_id, volume, path, data, file_size:
            drive(drive_id).create_file(volume, path, data, file_size),
        "append_file": lambda drive_id, volume, path, data:
            drive(drive_id).append_file(volume, path, data),
        "read_file_stream": lambda drive_id, volume, path, offset, length:
            drive(drive_id).read_file_stream(volume, path, offset, length),
        "read_segment": lambda drive_id, sid, off, length:
            drive(drive_id).read_segment(sid, off, length),
        "rename_file": lambda drive_id, src_volume, src_path, dst_volume,
            dst_path: drive(drive_id).rename_file(
                src_volume, src_path, dst_volume, dst_path),
        "delete": lambda drive_id, volume, path, recursive:
            drive(drive_id).delete(volume, path, recursive),
        "stat_info_file": lambda drive_id, volume, path:
            drive(drive_id).stat_info_file(volume, path),
        "rename_data": lambda drive_id, src_volume, src_path, fi,
            dst_volume, dst_path: drive(drive_id).rename_data(
                src_volume, src_path, FileInfo.from_dict(fi), dst_volume,
                dst_path),
        "write_metadata": lambda drive_id, volume, path, fi:
            drive(drive_id).write_metadata(volume, path,
                                           FileInfo.from_dict(fi)),
        "update_metadata": lambda drive_id, volume, path, fi:
            drive(drive_id).update_metadata(volume, path,
                                            FileInfo.from_dict(fi)),
        "read_version": lambda drive_id, volume, path, version_id,
            read_data: drive(drive_id).read_version(
                volume, path, version_id, read_data).to_dict(),
        "list_versions": lambda drive_id, volume, path: [
            fi.to_dict()
            for fi in drive(drive_id).list_versions(volume, path)],
        "delete_version": lambda drive_id, volume, path, fi,
            force_del_marker: drive(drive_id).delete_version(
                volume, path, FileInfo.from_dict(fi), force_del_marker),
        "verify_file": lambda drive_id, volume, path, fi:
            drive(drive_id).verify_file(volume, path,
                                        FileInfo.from_dict(fi)),
        "check_parts": lambda drive_id, volume, path, fi:
            drive(drive_id).check_parts(volume, path,
                                        FileInfo.from_dict(fi)),
        "walk_dir": lambda drive_id, volume, base_dir, recursive:
            list(drive(drive_id).walk_dir(volume, base_dir, recursive)),
        "walk_entries": lambda drive_id, volume, base_dir, recursive,
            versions: list(drive(drive_id).walk_entries(
                volume, base_dir, recursive, versions)),
        "tmp_dir": lambda drive_id: drive(drive_id).tmp_dir(),
        "clean_tmp": lambda drive_id, rel_dir:
            drive(drive_id).clean_tmp(rel_dir),
        "get_disk_id": lambda drive_id: drive(drive_id).get_disk_id(),
        "set_disk_id": lambda drive_id, disk_id:
            drive(drive_id).set_disk_id(disk_id),
    }
    rpc.register("storage", methods)

    # bulk shard transfer endpoints: raw HTTP bodies, one materialization
    # per side (storage-rest chunked streams, cmd/storage-rest-server.go)
    def raw_write(params, data):
        d = drive(params["drive_id"])
        if params.get("op") == "append":
            d.append_file(params["volume"], params["path"], data)
        elif params.get("op") == "commit":
            # single-RPC PUT commit: part bytes + version merge in one
            # round trip (vs tmp_dir + create_file + rename_data = 3)
            d.write_data_commit(params["volume"], params["path"],
                                FileInfo.from_dict(params["fi"]), data)
        elif params.get("op") == "packed":
            # packed small-object commit: the shard joins the owning
            # node's segment file, grouping with that node's local
            # traffic (the group-commit plane is per physical drive)
            d.write_packed(params["volume"], params["path"],
                           FileInfo.from_dict(params["fi"]), data)
        else:
            d.create_file(params["volume"], params["path"], data,
                          params.get("file_size", -1))
        return b""

    def raw_read(params, data):
        d = drive(params["drive_id"])
        volume, path = params["volume"], params["path"]
        offset, length = params["offset"], params["length"]
        chunk = int(params.get("resp_stream") or 0)
        if not chunk or length <= chunk:
            return d.read_file_stream(volume, path, offset, length)
        # streamed reply: the shard leaves the drive chunk-by-chunk —
        # never materialized server-side, ONE open for the window
        # (read_stream).  The FIRST chunk is pulled EAGERLY so
        # FileNotFound/FileCorrupt stay typed errors (after the 200
        # goes out, a failure can only close the connection).
        it = d.read_stream(volume, path, offset, length, chunk)
        first = next(it)

        def rest():
            yield first
            yield from it

        return (length, rest())

    def stream_write(params, frames):
        """Framed-streaming twin of raw_write (parallel/rpc.py wire
        format): every frame lands on the drive as it arrives.  The
        gated commit reads its final version dict from the TRAILER
        frame — the client resolves its etag gate only after the part
        bytes crossed the wire, so the md5 overlaps the remote leg of
        the fan-out exactly as it overlaps the local one."""
        d = drive(params["drive_id"])
        volume, path = params["volume"], params["path"]
        op = params.get("op")
        if op == "append":
            d.write_stream(volume, path, frames, op="append")
        elif op == "commit":
            gate = None
            if params.get("trailer"):
                def gate():
                    return msgpack.unpackb(frames.read_trailer(),
                                           raw=False)
            d.write_data_commit(volume, path,
                                FileInfo.from_dict(params["fi"]),
                                frames, meta_gate=gate)
        else:
            d.write_stream(volume, path, frames, op="create",
                           file_size=params.get("file_size", -1))
        return b""

    rpc.register_raw("storage-write", raw_write)
    rpc.register_raw("storage-read", raw_read)
    rpc.register_raw_stream("storage-write", stream_write)


class RemoteStorage(StorageAPI):
    """StorageAPI over RPC to a peer node's drive
    (cmd/storage-rest-client.go)."""

    def __init__(self, client: RPCClient, drive_id: str):
        self._c = client
        self.drive_id = drive_id

    # read-only methods may retry transparently on a stale pooled
    # connection; mutations must never execute twice
    _IDEMPOTENT = {
        "disk_info", "list_vols", "stat_vol", "list_dir", "read_all",
        "read_file_stream", "read_segment", "stat_info_file",
        "read_version", "list_versions", "verify_file", "check_parts",
        "walk_dir", "walk_entries", "get_disk_id",
    }

    def _call(self, method: str, **kwargs):
        # client-observed drive call (drive latency incl. the wire);
        # the owning node's XLStorage times the drive-local twin.  The
        # last-minute window stays on the owning node — remote drives
        # must not be double-counted in disk latency stats.
        t0 = time.monotonic_ns()
        err = ""
        try:
            return self._c.call("storage", method, drive_id=self.drive_id,
                                _idempotent=method in self._IDEMPOTENT,
                                **kwargs)
        except RPCError as e:
            err = f"{e.error_type}: {e.message}"
            raise self._map_err(e) from e
        finally:
            self._span(method, t0, err, kwargs)

    def _raw(self, name: str, params: dict, body=b"") -> bytes:
        t0 = time.monotonic_ns()
        err = ""
        try:
            return self._c.raw_call(
                name, {"drive_id": self.drive_id, **params}, body,
                idempotent=(name == "storage-read"))
        except RPCError as e:
            err = f"{e.error_type}: {e.message}"
            raise self._map_err(e) from e
        finally:
            self._span(name, t0, err, params,
                       nbytes=body.sent if isinstance(body, StreamBody)
                       else len(body))

    def _stream_body(self, data, chunk: int,
                     trailer_fn=None) -> StreamBody | None:
        """Framed streaming body over ``chunk``-sized slices of
        ``data`` — zero-copy memoryview slices, re-iterable so breaker
        retries can replay.  None when the body is too small to be
        worth a stream (or not a flat buffer): callers fall back to the
        materialized raw call."""
        if not chunk:
            return None
        try:
            mv = memoryview(data).cast("B")
        except (TypeError, ValueError):
            return None
        if len(mv) <= chunk and trailer_fn is None:
            return None

        def chunks():
            for off in range(0, len(mv), chunk):
                yield mv[off:off + chunk]

        return StreamBody(chunks, trailer_fn)

    def _span(self, method: str, t0: int, err: str, params: dict,
              nbytes: int = 0) -> None:
        """The caller's wall of one RPC: always into
        ``mt_drive_call_seconds{op,kind="remote"}`` (``op`` is the RPC's
        name: a storage method, or ``storage-read`` / ``storage-write``
        for the raw body calls); the ``storage`` span only for a
        subscriber."""
        dt = time.monotonic_ns() - t0   # t0 is monotonic; wall clock
        _metrics.observe("mt_drive_call_seconds",
                         {"op": method, "kind": "remote"}, dt / 1e9,
                         buckets=KERNEL_BUCKETS)
        if not _trace.active():
            return
        _trace.publish_span(_trace.make_span(  # only for the timestamp
            "storage", f"storage.{method}",
            start_ns=_trace.now_ns() - dt,
            duration_ns=dt, input_bytes=nbytes,
            error=err,
            detail={"drive": self.endpoint(), "remote": True,
                    "volume": params.get("volume", ""),
                    "path": params.get("path", "")}))

    def _map_err(self, e: RPCError) -> Exception:
        cls = _ERR_TYPES.get(e.error_type)
        if cls is not None:
            return cls(e.message)
        return serrors.DiskNotFound(
            f"{self._c.endpoint}/{self.drive_id}: {e}")

    # identity / health
    def is_online(self) -> bool:
        return self._c.is_online()

    def endpoint(self) -> str:
        return f"{self._c.endpoint}/{self.drive_id}"

    def is_local(self) -> bool:
        return False

    def get_disk_id(self) -> str:
        return self._call("get_disk_id")

    def set_disk_id(self, disk_id: str) -> None:
        self._call("set_disk_id", disk_id=disk_id)

    def disk_info(self) -> DiskInfo:
        return DiskInfo(**self._call("disk_info"))

    def close(self) -> None:
        pass

    # volumes
    def make_vol(self, volume):
        self._call("make_vol", volume=volume)

    def list_vols(self):
        return [VolInfo(v["name"], v["created"])
                for v in self._call("list_vols")]

    def stat_vol(self, volume):
        v = self._call("stat_vol", volume=volume)
        return VolInfo(v["name"], v["created"])

    def delete_vol(self, volume, force=False):
        self._call("delete_vol", volume=volume, force=force)

    # files
    def list_dir(self, volume, dir_path, count=-1):
        return self._call("list_dir", volume=volume, dir_path=dir_path,
                          count=count)

    def read_all(self, volume, path):
        return self._call("read_all", volume=volume, path=path)

    def write_all(self, volume, path, data):
        self._call("write_all", volume=volume, path=path, data=bytes(data))

    def create_file(self, volume, path, data, file_size=-1):
        body = self._stream_body(data, STREAM.chunk())
        self._raw("storage-write",
                  {"volume": volume, "path": path, "op": "create",
                   "file_size": file_size},
                  bytes(data) if body is None else body)

    def append_file(self, volume, path, data):
        body = self._stream_body(data, STREAM.chunk())
        self._raw("storage-write",
                  {"volume": volume, "path": path, "op": "append"},
                  bytes(data) if body is None else body)

    def read_file_stream(self, volume, path, offset, length):
        params = {"volume": volume, "path": path,
                  "offset": offset, "length": length}
        chunk = STREAM.chunk()
        if chunk and length > chunk:
            # streamed reply: the peer reads the shard off its drive
            # chunk-by-chunk instead of materializing it (the wire is
            # identical — Content-Length is known up front)
            params["resp_stream"] = chunk
        return self._raw("storage-read", params)

    def rename_file(self, src_volume, src_path, dst_volume, dst_path):
        self._call("rename_file", src_volume=src_volume, src_path=src_path,
                   dst_volume=dst_volume, dst_path=dst_path)

    def delete(self, volume, path, recursive=False):
        self._call("delete", volume=volume, path=path, recursive=recursive)

    def stat_info_file(self, volume, path):
        return self._call("stat_info_file", volume=volume, path=path)

    def write_data_commit(self, volume, path, fi, data,
                          shard_index=None, version_dict=None,
                          meta_gate=None):
        def _patched(base: dict) -> dict:
            d = dict(base)
            if shard_index is not None:
                d["ec"] = dict(d["ec"], index=shard_index)
            return d

        chunk = STREAM.chunk()
        if meta_gate is not None and chunk:
            # gated streamed commit: part frames cross the wire FIRST,
            # the gate resolves into the TRAILER frame — the md5 tail
            # overlaps the remote write exactly as it overlaps local
            # drives.  A gate abort (BadDigest) sends the abort marker;
            # the peer discards the partial data dir and no version is
            # ever visible.
            body = self._stream_body(
                data, chunk,
                trailer_fn=lambda: msgpack.packb(_patched(meta_gate()),
                                                 use_bin_type=True))
            if body is not None:
                self._raw("storage-write",
                          {"volume": volume, "path": path,
                           "op": "commit", "fi": _patched(fi.to_dict()),
                           "trailer": True}, body)
                return
        if meta_gate is not None:
            # materialized fallback: one RPC carries part bytes + final
            # version dict, so the gate must resolve before the wire
            # write; the md5 still overlaps the local drives' gated
            # writes running in the same fan-out
            version_dict = meta_gate()
        d = _patched(version_dict if version_dict is not None
                     else fi.to_dict())
        body = self._stream_body(data, chunk)
        self._raw("storage-write",
                  {"volume": volume, "path": path, "op": "commit",
                   "fi": d}, bytes(data) if body is None else body)

    def write_packed(self, volume, path, fi, data,
                     shard_index=None, version_dict=None):
        # packed small-object commit: the shard joins the OWNING node's
        # segment file, so it groups with that node's local traffic (the
        # group-commit plane is per physical drive, not per caller)
        d = dict(version_dict) if version_dict is not None \
            else fi.to_dict()
        if shard_index is not None:
            d["ec"] = dict(d["ec"], index=shard_index)
        body = self._stream_body(data, STREAM.chunk())
        self._raw("storage-write",
                  {"volume": volume, "path": path, "op": "packed",
                   "fi": d}, bytes(data) if body is None else body)

    def read_segment(self, sid, off, length):
        return self._call("read_segment", sid=sid, off=off, length=length)

    # metadata
    def rename_data(self, src_volume, src_path, fi, dst_volume, dst_path):
        self._call("rename_data", src_volume=src_volume, src_path=src_path,
                   fi=fi.to_dict(), dst_volume=dst_volume,
                   dst_path=dst_path)

    def write_metadata(self, volume, path, fi):
        self._call("write_metadata", volume=volume, path=path,
                   fi=fi.to_dict())

    def update_metadata(self, volume, path, fi):
        self._call("update_metadata", volume=volume, path=path,
                   fi=fi.to_dict())

    def read_version(self, volume, path, version_id=None, read_data=False):
        return FileInfo.from_dict(self._call(
            "read_version", volume=volume, path=path, version_id=version_id,
            read_data=read_data))

    def list_versions(self, volume, path):
        return [FileInfo.from_dict(d)
                for d in self._call("list_versions", volume=volume,
                                    path=path)]

    def delete_version(self, volume, path, fi, force_del_marker=False):
        self._call("delete_version", volume=volume, path=path,
                   fi=fi.to_dict(), force_del_marker=force_del_marker)

    # integrity
    def verify_file(self, volume, path, fi):
        self._call("verify_file", volume=volume, path=path, fi=fi.to_dict())

    def check_parts(self, volume, path, fi):
        self._call("check_parts", volume=volume, path=path,
                   fi=fi.to_dict())

    # walking
    def walk_dir(self, volume, base_dir="", recursive=True) -> Iterable[str]:
        return iter(self._call("walk_dir", volume=volume, base_dir=base_dir,
                               recursive=recursive))

    def walk_entries(self, volume, base_dir="", recursive=True,
                     versions=False) -> Iterable[dict]:
        return iter(self._call("walk_entries", volume=volume,
                               base_dir=base_dir, recursive=recursive,
                               versions=versions))

    # staging
    def tmp_dir(self) -> str:
        return self._call("tmp_dir")

    def clean_tmp(self, rel_dir: str) -> None:
        self._call("clean_tmp", rel_dir=rel_dir)
