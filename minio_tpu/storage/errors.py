"""Storage error catalogue — mirrors cmd/storage-errors.go semantics.

Typed exceptions instead of Go sentinel errors; the quorum/reduce logic in
the object layer matches on these types the way the reference matches on
sentinel identity (cmd/erasure-metadata-utils.go reduceErrs).
"""

from __future__ import annotations


class StorageError(OSError):
    """Base class for all per-drive storage errors."""


class DiskNotFound(StorageError):
    """errDiskNotFound: drive offline / not reachable."""


class UnformattedDisk(StorageError):
    """errUnformattedDisk: fresh drive without format.json."""


class CorruptedFormat(StorageError):
    """errCorruptedFormat: unreadable format.json."""


class DiskFull(StorageError):
    """errDiskFull."""


class VolumeNotFound(StorageError):
    """errVolumeNotFound: bucket does not exist on this drive."""


class VolumeExists(StorageError):
    """errVolumeExists."""


class VolumeNotEmpty(StorageError):
    """errVolumeNotEmpty."""


class FileNotFound(StorageError):
    """errFileNotFound: object/shard path missing."""


class FileVersionNotFound(StorageError):
    """errFileVersionNotFound: version id not present in xl.meta."""


class FileNameTooLong(StorageError):
    """errFileNameTooLong."""


class FileAccessDenied(StorageError):
    """errFileAccessDenied."""


class FileCorrupt(StorageError):
    """errFileCorrupt: bitrot verification failed / truncated shard."""


class IsNotRegular(StorageError):
    """errIsNotRegular: path exists but is not a regular file/dir as needed."""


class PathNotEmpty(StorageError):
    """errPathNotEmpty (object path has children)."""


class DiskAccessDenied(StorageError):
    """errDiskAccessDenied."""


class FaultyDisk(StorageError):
    """errFaultyDisk: drive misbehaving (used by fault injection too)."""


class MethodNotAllowed(StorageError):
    """errMethodNotAllowed (e.g. delete-marker read)."""


class DoneForNow(Exception):
    """errDoneForNow: listing pagination sentinel."""
