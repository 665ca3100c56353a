"""Shared build-on-demand loader for the native (C/C++) helper libraries.

Six subsystems carry native kernels — snappy compression
(native/snappy.cc), HighwayHash (hashing/native/highwayhash.c), the
GF(2^8) erasure matmul (native/gf8.cc), multi-buffer md5
(native/md5mb.cc), the NDJSON scanner (native/jsonscan.cc) and the
drive syscalls issued below the interpreter (native/syncwave.c: the
group commit's fsync waves, an op body's file landings and a quorum
metadata read's xl.meta read wave) — the
roles the reference fills with assembly-accelerated Go modules
(SURVEY.md §2.4).  They all share one loading discipline, implemented
once here:

* the built file is keyed by its source's content (and the compiler and
  flags): ``libfoo.so`` is built and loaded as ``libfoo.<hash>.so``, so
  a library built from other source — copied along with the tree, or
  left over from an earlier checkout — is never loaded, whatever its
  mtime.  Nothing built is committed; native/build/ is git-ignored;
* compile to a temp file and os.replace it (atomic under concurrent
  processes);
* honor MT_NATIVE=0 (force the pure-Python fallbacks) and CC;
* never raise: a missing compiler returns None and callers fall back —
  but never silently: ``status()`` says, per library, whether it loaded
  and why not (admin ``info`` and ``chip_smoke.py`` read it back).

Thread-safe: a per-path lock guarantees a library is built and loaded
exactly once, and concurrent first callers WAIT for the build instead of
silently taking the slow path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_meta_lock = threading.Lock()
_locks: dict[str, threading.Lock] = {}
_cache: dict[str, ctypes.CDLL | None] = {}
_status: dict[str, dict] = {}


def status() -> dict[str, dict]:
    """Per library this process tried to load: the file, whether it
    loaded, and the reason when it did not."""
    with _meta_lock:
        return {k: dict(v) for k, v in _status.items()}


def load(src: str, so: str, timeout: int = 120) -> ctypes.CDLL | None:
    """Build (unless already built from this very source) and load
    `src` as `so` (content-keyed, see above); None when unavailable.

    Idempotent per `so` path; concurrent callers of the SAME library
    block until the first build finishes rather than observing a
    half-initialized state — a slow compile of one library never stalls
    loads of the others."""
    # sanitizer/CI hook: MT_NATIVE_BUILD_DIR redirects the compiled .so
    # (so an instrumented build never clobbers the production cache)
    # and MT_NATIVE_CFLAGS appends flags, e.g.
    # "-fsanitize=address,undefined" (tests/test_sanitizers.py tier,
    # the buildscripts/race.sh role)
    build_dir = os.environ.get("MT_NATIVE_BUILD_DIR", "")
    if build_dir:
        so = os.path.join(build_dir, os.path.basename(so))
    extra = os.environ.get("MT_NATIVE_CFLAGS", "").split()
    with _meta_lock:
        lock = _locks.setdefault(so, threading.Lock())
    with lock:
        if so in _cache:
            return _cache[so]
        lib, built, err = None, so, ""
        if os.environ.get("MT_NATIVE", "1") == "0":
            err = "MT_NATIVE=0"
        else:
            try:
                cc = os.environ.get("CC", "g++" if src.endswith(
                    (".cc", ".cpp")) else "cc")
                cmd = [cc, "-O3", "-shared", "-fPIC", *extra]
                with open(src, "rb") as f:
                    key = hashlib.sha256(
                        f.read() + "\0".join(cmd).encode()).hexdigest()[:16]
                built = f"{so.removesuffix('.so')}.{key}.so"
                if not os.path.exists(built):
                    os.makedirs(os.path.dirname(built), exist_ok=True)
                    tmp = built + f".tmp{os.getpid()}"
                    subprocess.run(  # mt-lint: ok(lock-discipline) one-time lazy build: waiters NEED the .so this compile produces; double-checked via _cache so it runs once per process
                        [*cmd, "-o", tmp, src],
                        check=True, capture_output=True, timeout=timeout)
                    os.replace(tmp, built)
                lib = ctypes.CDLL(built)
            except Exception as e:  # noqa: BLE001 — fallback path is Python; the reason is kept for status()
                err = f"{type(e).__name__}: {e}"
                stderr = getattr(e, "stderr", None)
                if stderr:
                    err += " | " + stderr.decode(errors="replace")[-400:]
        _cache[so] = lib
        with _meta_lock:
            _status[os.path.basename(so)] = {
                "file": built, "loaded": lib is not None, "error": err}
        return lib
