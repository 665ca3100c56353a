"""Native toolchain smoke: every C/C++ helper (native/ and
hashing/native/highwayhash.c — the bit-identity oracle, no longer a
committed binary) must compile from a cold cache and load (utils/nativelib.py discipline), so a broken
toolchain is caught HERE with a named reason instead of silently
degrading every consumer to its Python fallback — and a host with no
compiler degrades to the fallbacks instead of failing tier-1.
"""

import ctypes
import os
import shutil

import pytest

from minio_tpu.utils import nativelib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

SOURCES = {
    "gf8.cc": "mt_gf8_matmul",
    "snappy.cc": "mt_snappy_compress",
    "jsonscan.cc": "mt_ndjson_filter",
    "md5mb.cc": "mt_md5mb_update",
    os.path.join(REPO, "minio_tpu", "hashing", "native",
                 "highwayhash.c"): "mt_hh256_verify_framed",
}


def _have_compiler() -> bool:
    return all(shutil.which(os.environ.get("CC", cc)) is not None
               for cc in ("g++", "cc"))


pytestmark = pytest.mark.skipif(
    not _have_compiler(), reason="no C++ compiler on this host "
    "(native kernels degrade to Python/hashlib fallbacks)")


@pytest.mark.parametrize("src,symbol", sorted(SOURCES.items()))
def test_source_compiles_cold_and_exports_symbol(tmp_path, monkeypatch,
                                                 src, symbol):
    """Cold build into a scratch dir (MT_NATIVE_BUILD_DIR redirect, the
    sanitizer-tier hook) — proves the checked-in source still compiles
    on this image, independent of any cached .so."""
    monkeypatch.setenv("MT_NATIVE_BUILD_DIR", str(tmp_path))
    path = os.path.join(NATIVE, src)       # absolute src passes through
    name = "lib_smoke_" + os.path.basename(src) + ".so"
    lib = nativelib.load(path, os.path.join(str(tmp_path), name))
    st = nativelib.status()[name]
    if lib is None:
        pytest.fail(f"{src} failed to build: {st['error'][-2000:]}")
    assert getattr(lib, symbol, None) is not None
    # built under a name keyed by the source's content, and reported
    assert st["loaded"] and os.path.exists(st["file"])
    assert os.path.basename(st["file"]) != name


def test_stale_library_is_never_loaded(tmp_path, monkeypatch):
    """A .so sitting at the nominal path — copied along with a tree,
    built from other source, newer than the source — must not be what
    gets loaded: the loader only opens the file keyed by THIS source."""
    monkeypatch.setenv("MT_NATIVE_BUILD_DIR", str(tmp_path))
    src = tmp_path / "probe.c"
    src.write_text("int mt_probe(void) { return 1; }\n")
    so = str(tmp_path / "libprobe.so")
    with open(so, "wb") as f:
        f.write(b"not a shared object")       # stale/garbage, newer mtime
    lib = nativelib.load(str(src), so)
    assert lib is not None and lib.mt_probe() == 1
    first = nativelib.status()["libprobe.so"]["file"]
    # the source changes -> another key -> a fresh build, even though
    # the old library is still there and newer than nothing
    src.write_text("int mt_probe(void) { return 2; }\n")
    nativelib._cache.pop(so)
    lib2 = nativelib.load(str(src), so)
    assert lib2.mt_probe() == 2
    assert nativelib.status()["libprobe.so"]["file"] != first


def test_md5_core_digest_after_cold_build(tmp_path, monkeypatch):
    """The freshly-built md5 core (not the cached production .so) must
    agree with hashlib — catches a miscompiling toolchain, not just a
    missing one."""
    import hashlib
    monkeypatch.setenv("MT_NATIVE_BUILD_DIR", str(tmp_path))
    lib = nativelib.load(os.path.join(NATIVE, "md5mb.cc"),
                         os.path.join(str(tmp_path), "libmtmd5.so"))
    assert lib is not None
    lib.mt_md5_state_size.restype = ctypes.c_size_t
    lib.mt_md5_oneshot.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                   ctypes.c_char_p]
    msg = b"The quick brown fox jumps over the lazy dog" * 1000
    out = ctypes.create_string_buffer(16)
    lib.mt_md5_oneshot(msg, len(msg), out)
    assert out.raw == hashlib.md5(msg).digest()


def test_device_md5_degrades_with_named_reason():
    """The device-MD5 rung is the top of the strict-ETag ladder
    (pipeline.md5_backend): with no usable jax device it must degrade
    with a NAMED reason — the same discipline this tier enforces for
    a missing compiler — and with one it must agree with hashlib."""
    import hashlib

    import numpy as np

    from minio_tpu.hashing import md5_device
    if not md5_device.available():
        reason = md5_device.unavailable_reason()
        assert reason, "unavailability must carry a named reason"
        pytest.skip(reason)

    def direct(h, words):
        return md5_device.advance(
            h[None], words[None],
            np.asarray([words.shape[0]]))[0]

    msg = b"The quick brown fox jumps over the lazy dog" * 100
    h = md5_device.MD5Device(msg, dispatch=direct)
    assert h.hexdigest() == hashlib.md5(msg).hexdigest()


def test_no_compiler_degrades_to_hashlib(monkeypatch):
    """MT_NATIVE=0 (the no-toolchain path): md5fast must hand back
    hashlib digests, never raise."""
    import hashlib

    from minio_tpu.hashing import md5fast
    monkeypatch.setattr(md5fast, "_LIB", None)
    monkeypatch.setattr(md5fast, "_LIB_TRIED", True)
    assert md5fast.md5(b"x").hexdigest() == hashlib.md5(b"x").hexdigest()
