"""Sanitizer tier for the native libraries (buildscripts/race.sh role).

The C/C++ libraries (native/gf8.cc, native/snappy.cc,
native/jsonscan.cc, native/syncwave.c, hashing/native/highwayhash.c)
are rebuilt with
``-fsanitize=address,undefined`` into a scratch build dir
(MT_NATIVE_BUILD_DIR) and exercised — through their normal Python
bindings, under concurrent load (sixteen threads landing op-body files
through syncwave.c at once, and a GET's shard read wave meeting a
corrupt frame, a short file and a missing one, among them) — in a
subprocess running with libasan preloaded.  Any ASan/UBSan report fails the run.

A canary proves the harness has teeth: a deliberately buggy library
built and driven the same way MUST be caught.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# slow: full ASan/UBSan rebuilds of every native library — runs in the
# full tier, not the tier-1 `-m 'not slow'` budget (VERDICT weak #5)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _libasan() -> str | None:
    try:
        out = subprocess.run(["g++", "-print-file-name=libasan.so"],
                             capture_output=True, text=True, timeout=30)
        path = out.stdout.strip()
        return path if path and os.path.exists(path) else None
    except (OSError, subprocess.TimeoutExpired):
        return None


asan = pytest.mark.skipif(_libasan() is None,
                          reason="libasan not available")


def _run_sanitized(code: str, tmp_path) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": _libasan(),
        "MT_NATIVE_BUILD_DIR": str(tmp_path / "san-build"),
        "MT_NATIVE_CFLAGS":
            "-fsanitize=address,undefined -fno-sanitize-recover=all -g",
        # python itself leaks by design at exit; halt_on_error keeps
        # real findings fatal
        "ASAN_OPTIONS": "detect_leaks=0:halt_on_error=1:abort_on_error=1",
        "UBSAN_OPTIONS": "halt_on_error=1:abort_on_error=1",
        "JAX_PLATFORMS": "cpu",
    })
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)


WORKLOAD = textwrap.dedent("""
    import os, threading
    import numpy as np

    errors = []

    def gf8_work():
        from minio_tpu.ops import gf8_native, gf8
        assert gf8_native.available(), "gf8 sanitized build failed"
        M = np.asarray(gf8.rs_matrix(8, 12))[8:]
        rng = np.random.default_rng(0)
        for n in (1, 31, 64, 4096, 87382):      # incl. GFNI tail sizes
            B = rng.integers(0, 256, (8, n), dtype=np.uint8)
            out = np.empty((4, n), dtype=np.uint8)
            gf8_native.matmul_into(M, B, out)
            exp = gf8_native.matmul(M, B)
            assert np.array_equal(out, exp)

    def snappy_work():
        from minio_tpu import compress
        if not compress.native_available():
            return
        for size in (0, 1, 100, 70000):
            blob = os.urandom(size // 2) * 2
            assert compress.decompress_block(
                compress.compress_block(blob)) == blob
            assert compress.decompress_stream(
                compress.compress_stream(blob)) == blob

    def hh_work():
        from minio_tpu.hashing import highwayhash as hh
        for size in (0, 1, 31, 32, 33, 1024, 87382):
            hh.hh256(os.urandom(size))

    def jsonscan_work():
        from minio_tpu.s3select import records
        data = b'\\n'.join(
            b'{"k":"v%d","n":%d}' % (i, i) for i in range(200)) + b'\\n'
        records.ndjson_prefilter(data, "k", "=", "v7")
        records.ndjson_prefilter(data, "n", ">", 100)

    def syncwave_work():
        import tempfile
        from minio_tpu.storage import commit
        assert commit._wave_lib() is not None, "syncwave build failed"
        with tempfile.TemporaryDirectory() as d:
            for n in (1, 7, 8, 9, 40):          # around the slice count
                fds = [os.open(os.path.join(d, f"f{i}"),
                               os.O_CREAT | os.O_WRONLY) for i in range(n)]
                assert commit.sync_files(fds) == [0] * n
                commit.sync_dirs([d] * n + [os.path.join(d, "gone")])

    def landing_work():
        # an op body's landings (commit.land_part / land_file), with and
        # without a collector, and every failing step they can be made
        # to take: no descriptor may stay open, no byte be misread
        import tempfile
        from minio_tpu.storage import commit
        assert commit._wave_lib() is not None, "syncwave build failed"
        body = np.frombuffer(os.urandom(300_000), dtype=np.uint8)
        with tempfile.TemporaryDirectory() as d:
            for i in range(6):
                obj = os.path.join(d, f"o{i}")
                part = obj + "/dd/part.1"
                col = commit.GroupCollector() if i % 2 else None
                if col is not None:
                    commit.arm(col)
                try:
                    assert commit.land_part(obj, obj + "/dd", part, body)
                    commit.land_file(obj + "/xl.meta.tmp", bytes(body[:600]))
                    for bad, exc in (
                            (lambda: commit.land_part(
                                obj, obj + "/dd", part, body),
                             FileExistsError),
                            (lambda: commit.land_part(
                                d + "/gone/o", d + "/gone/o/dd",
                                d + "/gone/o/dd/part.1", body),
                             FileNotFoundError),
                            (lambda: commit.land_file(obj, body),
                             IsADirectoryError),
                            (lambda: commit.land_file("/dev/full", body),
                             OSError)):
                        try:
                            bad()
                        except exc:
                            pass
                        else:
                            raise AssertionError("a failing step passed")
                finally:
                    commit.disarm()
                if col is not None:
                    assert len(col._fds) == 2
                    col.flush()
                with open(part, "rb") as f:
                    assert f.read() == body.tobytes()

    def readwave_work():
        # a quorum metadata read's wave (mt_read_files): files that fit
        # their slot, fill it exactly, outgrow it; a directory; a gap
        import tempfile
        from minio_tpu.storage import commit, xl_storage
        from minio_tpu.storage.xl_meta import XLMeta
        assert commit._wave_lib() is not None, "syncwave build failed"
        with tempfile.TemporaryDirectory() as d:
            disks, want = [], []
            for i in range(16):
                root = os.path.join(d, f"d{i}")
                os.makedirs(os.path.join(root, "bkt", "obj"))
                disks.append(xl_storage.XLStorage(root))
                meta = os.path.join(root, "bkt", "obj", "xl.meta")
                if i == 14:
                    os.mkdir(meta)
                elif i < 14:
                    blob = XLMeta([{"vid": "", "mt": i, "type": "object",
                                    "data": os.urandom(i * 2000)}]).dump()
                    with open(meta, "wb") as f:
                        f.write(blob)
                want.append(i < 14)
            got = xl_storage.read_version_wave(disks, "bkt", "obj")
            assert [fi is not None for fi, *_ in got] == want, got

    def shardwave_work():
        # a GET's shard read wave (mt_read_verify_ranges): whole windows,
        # a window of one frame and a short tail frame; a corrupt frame,
        # a short file, a missing one
        import tempfile
        from minio_tpu.hashing import bitrot, highwayhash
        from minio_tpu.storage import commit, xl_storage
        assert commit._wave_lib() is not None, "syncwave build failed"
        assert highwayhash.verify_framed_address() is not None
        ssize, blocks = 5462, 5
        with tempfile.TemporaryDirectory() as d:
            disks, want = [], []
            payload = os.urandom(ssize * blocks - 1000)
            framed = highwayhash.hh256_frame(payload, ssize)
            for i in range(12):
                root = os.path.join(d, f"d{i}")
                os.makedirs(os.path.join(root, "bkt", "obj"))
                disks.append(xl_storage.XLStorage(root))
                blob = bytearray(framed)
                if i == 9:
                    blob[32 + ssize + 32 + 7] ^= 0xFF   # frame 2
                elif i == 10:
                    blob = blob[:len(blob) // 2]        # short
                if i != 11:                             # missing
                    with open(os.path.join(root, "bkt", "obj",
                                           "part.1"), "wb") as f:
                        f.write(blob)
            for b0, b1 in ((0, blocks), (0, 1), (blocks - 1, blocks),
                           (1, 3)):
                off = b0 * ssize
                seg = min(b1 * ssize, len(payload)) - off
                flen = seg + (b1 - b0) * 32
                items = [("read_file_stream", "bkt", "obj/part.1",
                          off + b0 * 32)] * 12
                got = xl_storage.read_shard_wave(disks, items, flen, seg,
                                                 ssize)
                for i, (row, err, *_) in enumerate(got):
                    ok = i < 9 or (i == 9 and not b0 <= 1 < b1) \
                        or (i == 10 and off + b0 * 32 + flen
                            <= len(framed) // 2)
                    assert (err is None) == ok, (i, b0, b1, err)
                    if ok:
                        assert row.tobytes() == payload[off:off + seg]

    def run(fn):
        try:
            for _ in range(5):
                fn()
        except Exception as e:      # noqa: BLE001
            errors.append(f"{fn.__name__}: {e!r}")

    threads = [threading.Thread(target=run, args=(f,))
               for f in (gf8_work, snappy_work, hh_work, jsonscan_work,
                         syncwave_work, readwave_work, shardwave_work)
               for _ in range(3)]
    # one writer thread per drive of a 16-drive set, landing at once
    threads += [threading.Thread(target=run, args=(landing_work,))
                for _ in range(16)]
    for t in threads: t.start()
    for t in threads: t.join()
    assert not errors, errors
    print("SANITIZED-WORKLOAD-OK")
""")


@asan
def test_native_libs_clean_under_asan_ubsan(tmp_path):
    res = _run_sanitized(WORKLOAD, tmp_path)
    assert "SANITIZED-WORKLOAD-OK" in res.stdout, \
        f"stdout={res.stdout[-2000:]}\nstderr={res.stderr[-4000:]}"
    for marker in ("AddressSanitizer", "runtime error:",
                   "SUMMARY: UndefinedBehaviorSanitizer"):
        assert marker not in res.stderr, res.stderr[-4000:]
    assert res.returncode == 0


CANARY_SRC = textwrap.dedent("""
    #include <cstring>
    extern "C" int mt_canary(const unsigned char* src, int n) {
        unsigned char buf[8];
        std::memcpy(buf, src, n);     // n > 8 overflows the stack buf
        return buf[0];
    }
""")

CANARY_DRIVER = textwrap.dedent("""
    import ctypes, os
    from minio_tpu.utils import nativelib
    src = os.environ["CANARY_SRC"]
    so = os.path.join(os.environ["MT_NATIVE_BUILD_DIR"], "libcanary.so")
    lib = nativelib.load(src, so)
    assert lib is not None, "canary build failed"
    lib.mt_canary(b"x" * 64, 64)      # overflow -> ASan must abort
    print("CANARY-SURVIVED")          # must never print
""")


@asan
def test_harness_catches_injected_overflow(tmp_path):
    """The tier is only evidence if it FAILS on a real bug."""
    src = tmp_path / "canary.cc"
    src.write_text(CANARY_SRC)
    env_extra = {"CANARY_SRC": str(src)}
    code = CANARY_DRIVER
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": _libasan(),
        "MT_NATIVE_BUILD_DIR": str(tmp_path / "san-build"),
        "MT_NATIVE_CFLAGS":
            "-fsanitize=address,undefined -fno-sanitize-recover=all -g",
        "ASAN_OPTIONS": "detect_leaks=0:halt_on_error=1:abort_on_error=1",
        **env_extra,
    })
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0, "injected overflow was NOT caught"
    assert "CANARY-SURVIVED" not in res.stdout
    assert "AddressSanitizer" in res.stderr, res.stderr[-2000:]
