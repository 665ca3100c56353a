"""StorageAPI — the per-drive contract (cmd/storage-interface.go:25).

Every drive (local posix dir today, remote RPC later) implements this
surface.  The object layer only talks to drives through it, which is what
makes fault injection (FaultyDisk), the disk-id check decorator, and the
remote storage client drop-in replacements, as in the reference.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Iterable

from .datatypes import FileInfo


@dataclass
class DiskInfo:
    """cmd/storage-datatypes.go DiskInfo."""
    total: int = 0
    free: int = 0
    used: int = 0
    free_inodes: int = 0
    fs_type: str = ""
    root_disk: bool = False
    healing: bool = False
    endpoint: str = ""
    mount_path: str = ""
    disk_id: str = ""
    error: str = ""


@dataclass
class VolInfo:
    name: str
    created: int = 0  # unix ns


@dataclass
class FilesInfo:
    files: list[FileInfo] = field(default_factory=list)
    is_truncated: bool = False


class StorageAPI(abc.ABC):
    """Abstract drive (cmd/storage-interface.go:25-92)."""

    # -- identity / health -------------------------------------------------

    @abc.abstractmethod
    def is_online(self) -> bool: ...

    @abc.abstractmethod
    def endpoint(self) -> str: ...

    @abc.abstractmethod
    def is_local(self) -> bool: ...

    @abc.abstractmethod
    def get_disk_id(self) -> str: ...

    @abc.abstractmethod
    def set_disk_id(self, disk_id: str) -> None: ...

    @abc.abstractmethod
    def disk_info(self) -> DiskInfo: ...

    @abc.abstractmethod
    def close(self) -> None: ...

    # -- volume ops --------------------------------------------------------

    @abc.abstractmethod
    def make_vol(self, volume: str) -> None: ...

    @abc.abstractmethod
    def list_vols(self) -> list[VolInfo]: ...

    @abc.abstractmethod
    def stat_vol(self, volume: str) -> VolInfo: ...

    @abc.abstractmethod
    def delete_vol(self, volume: str, force: bool = False) -> None: ...

    # -- file ops ----------------------------------------------------------

    @abc.abstractmethod
    def list_dir(self, volume: str, dir_path: str,
                 count: int = -1) -> list[str]: ...

    @abc.abstractmethod
    def read_all(self, volume: str, path: str) -> bytes: ...

    @abc.abstractmethod
    def write_all(self, volume: str, path: str, data: bytes) -> None: ...

    @abc.abstractmethod
    def create_file(self, volume: str, path: str, data: bytes,
                    file_size: int = -1) -> None: ...

    @abc.abstractmethod
    def append_file(self, volume: str, path: str, data: bytes) -> None: ...

    @abc.abstractmethod
    def read_file_stream(self, volume: str, path: str, offset: int,
                         length: int) -> bytes: ...

    @abc.abstractmethod
    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None: ...

    @abc.abstractmethod
    def delete(self, volume: str, path: str, recursive: bool = False) -> None: ...

    @abc.abstractmethod
    def stat_info_file(self, volume: str, path: str) -> int:
        """Size of a file; FileNotFound if missing."""

    # -- metadata (xl.meta journal) ops ------------------------------------

    @abc.abstractmethod
    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Atomic commit: move tmp data dir + merge version into xl.meta
        (cmd/xl-storage.go:1965 RenameData)."""

    def write_data_commit(self, volume: str, path: str, fi: FileInfo,
                          data, shard_index: int | None = None,
                          version_dict: dict | None = None,
                          meta_gate=None) -> None:
        """One-shot single-part PUT commit: part bytes + version merge.

        Default composition stages through tmp + rename_data (correct on
        any backend); local drives override with a direct write into the
        final data dir — safe because fi.data_dir is a fresh uuid and the
        version only becomes visible when xl.meta is atomically replaced,
        the same invariant rename_data relies on.  ``shard_index``
        overrides fi.erasure.index for this drive (the fan-out shares
        one FileInfo; see XLStorage.write_data_commit).

        ``meta_gate`` is the overlapped-PUT hook: a callable that blocks
        until the object's ETag md5 resolved and returns the FINAL
        version dict (or raises to abort before any version becomes
        visible).  Backends that can, write the part bytes first and
        gate only the metadata merge — the hash runs beside the data
        fan-out (pkg/hash/reader.go overlap); this default resolves the
        gate up front (no overlap, always correct).  A gate may carry
        ``ready()`` (would the call return at once?): a local drive
        under a group commit then does not park its writer thread on it
        but runs the merge once its batch's other bodies have run."""
        from .datatypes import ErasureInfo
        from .xl_storage import SYS_DIR as sys_vol
        if meta_gate is not None:
            version_dict = meta_gate()
        if shard_index is not None and fi.erasure.index != shard_index:
            fi = FileInfo(**{**fi.__dict__})
            fi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
            fi.erasure.index = shard_index
        tmp = self.tmp_dir()
        try:
            self.create_file(sys_vol, f"{tmp}/part.1", data)
            self.rename_data(sys_vol, tmp, fi, volume, path)
        finally:
            self.clean_tmp(tmp)

    def write_packed(self, volume: str, path: str, fi: FileInfo,
                     data, shard_index: int | None = None,
                     version_dict: dict | None = None) -> None:
        """Packed small-object commit: the framed shard rides the
        drive's append-only segment file and xl.meta's per-drive
        ``seg`` field points at the extent (XLStorage.write_packed).
        Default composition falls back to the inline-data precedent —
        the shard lands INSIDE xl.meta — which is correct on any
        backend (one metadata write, no orphanable files) and keeps
        the cross-drive consistency hash identical, since both
        ``inline`` and ``seg`` are per-drive payload fields."""
        from .datatypes import ErasureInfo
        if shard_index is not None and fi.erasure.index != shard_index:
            fi = FileInfo(**{**fi.__dict__})
            fi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
            fi.erasure.index = shard_index
        if version_dict is not None:
            vd = dict(version_dict)
            vd["ec"] = dict(vd["ec"])
            if shard_index is not None:
                vd["ec"]["index"] = shard_index
            fi = FileInfo.from_dict(vd)
        fi.data_dir = ""
        fi.inline_data = bytes(data) if not isinstance(data, bytes) \
            else data
        self.write_metadata(volume, path, fi)

    def read_segment(self, sid: int, off: int, length: int) -> bytes:
        """Read one packed extent; only backends that pack natively
        (XLStorage, and RemoteStorage forwarding to one) serve this."""
        raise NotImplementedError

    @abc.abstractmethod
    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None: ...

    @abc.abstractmethod
    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None: ...

    @abc.abstractmethod
    def read_version(self, volume: str, path: str,
                     version_id: str | None = None,
                     read_data: bool = False) -> FileInfo: ...

    @abc.abstractmethod
    def list_versions(self, volume: str, path: str) -> list[FileInfo]: ...

    @abc.abstractmethod
    def delete_version(self, volume: str, path: str, fi: FileInfo,
                       force_del_marker: bool = False) -> None: ...

    # -- integrity ---------------------------------------------------------

    @abc.abstractmethod
    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        """Full bitrot verification of all parts
        (cmd/xl-storage.go:2305 VerifyFile); raises FileCorrupt."""

    @abc.abstractmethod
    def check_parts(self, volume: str, path: str, fi: FileInfo) -> None:
        """Part files exist with expected sizes (CheckParts)."""

    # -- walking (listing support) ----------------------------------------

    @abc.abstractmethod
    def walk_dir(self, volume: str, base_dir: str = "",
                 recursive: bool = True) -> Iterable[str]:
        """Yield object meta paths under a prefix (cmd/metacache-walk.go)."""

    def walk_entries(self, volume: str, base_dir: str = "",
                     recursive: bool = True,
                     versions: bool = False) -> Iterable[dict]:
        """Walked objects with xl.meta-derived metadata in one pass:
        {"name", "fis": [FileInfo dicts]} per object — the listing
        resolve source (cmd/metacache-walk.go streams raw xl.meta)."""
        raise NotImplementedError
