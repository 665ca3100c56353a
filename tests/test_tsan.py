"""ThreadSanitizer tier for the native libraries — the thread-race half
of the buildscripts/race.sh role (the ASan/UBSan half lives in
tests/test_sanitizers.py).

The GIL-released C paths (native/gf8.cc matmuls, the framed
highwayhash verify/fill, snappy, jsonscan, native/syncwave.c's flush
waves and op-body landings) run concurrently in
production: every drive fan-out and every GET verify can execute them
from multiple threads at once.  This tier rebuilds them with
``-fsanitize=thread`` into a scratch dir and drives them from many
Python threads under a preloaded libtsan; any ThreadSanitizer report
fails the run.

Same canary discipline as the ASan tier: a deliberately racy library
driven the same way MUST be caught, or the tier is not evidence.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# slow: TSan rebuilds + multi-minute race-hunting subprocesses — runs
# in the full tier, not the tier-1 `-m 'not slow'` budget
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _libtsan() -> str | None:
    try:
        out = subprocess.run(["g++", "-print-file-name=libtsan.so"],
                             capture_output=True, text=True, timeout=30)
        path = os.path.realpath(out.stdout.strip())
        return path if path and os.path.exists(path) else None
    except (OSError, subprocess.TimeoutExpired):
        return None


tsan = pytest.mark.skipif(_libtsan() is None,
                          reason="libtsan not available")


def _run_tsan(code: str, tmp_path, extra_env=None
              ) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": _libtsan(),
        "MT_NATIVE_BUILD_DIR": str(tmp_path / "tsan-build"),
        "MT_NATIVE_CFLAGS": "-fsanitize=thread -g",
        # report_bugs stays on; exitcode marks any report even without
        # halting mid-workload
        "TSAN_OPTIONS": "halt_on_error=0:exitcode=66",
        "JAX_PLATFORMS": "cpu",
        **(extra_env or {}),
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
        assert gf8_native.available(), "gf8 tsan build failed"
        M = np.asarray(gf8.rs_matrix(8, 12))[8:]
        rng = np.random.default_rng(0)
        for _ in range(10):
            B = rng.integers(0, 256, (8, 87382), dtype=np.uint8)
            out = np.empty((4, 87382), dtype=np.uint8)
            gf8_native.matmul_into(M, B, out)

    def hh_work():
        # the framed fill + verify pair the PUT/GET hot paths run
        # concurrently across drive fan-out threads
        from minio_tpu.hashing import bitrot, highwayhash as hh
        for _ in range(10):
            data = os.urandom(300_000)
            framed = np.frombuffer(
                bitrot.streaming_encode(data, 4096),
                dtype=np.uint8).copy()
            assert hh.hh256_verify_framed(framed, 4096) == 0
            framed[:32] = 0
            hh.hh256_fill(framed, 4096)

    def snappy_work():
        from minio_tpu import compress
        if not compress.native_available():
            return
        for _ in range(10):
            blob = os.urandom(30000) * 2
            assert compress.decompress_stream(
                compress.compress_stream(blob)) == blob

    def jsonscan_work():
        from minio_tpu.s3select import records
        data = b'\\n'.join(
            b'{"k":"v%d","n":%d}' % (i, i) for i in range(500)) + b'\\n'
        for _ in range(10):
            records.ndjson_prefilter(data, "k", "=", "v7")

    def syncwave_work():
        # a flush wave's slices share the fd / path / errno arrays
        import tempfile
        from minio_tpu.storage import commit
        assert commit._wave_lib() is not None, "syncwave tsan build failed"
        with tempfile.TemporaryDirectory() as d:
            for _ in range(10):
                fds = [os.open(os.path.join(d, f"f{i}"),
                               os.O_CREAT | os.O_WRONLY) for i in range(40)]
                assert commit.sync_files(fds) == [0] * 40
                commit.sync_dirs([d] * 9)

    def landing_work():
        # an op body's landings (commit.land_part / land_file), with and
        # without a collector, and every failing step they can be made
        # to take: no descriptor may stay open, no byte be misread
        import tempfile
        from minio_tpu.storage import commit
        assert commit._wave_lib() is not None, "syncwave tsan build failed"
        body = np.frombuffer(os.urandom(300_000), dtype=np.uint8)
        with tempfile.TemporaryDirectory() as d:
            for i in range(10):
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

    def run(fn):
        try:
            fn()
        except Exception as e:      # noqa: BLE001
            errors.append(f"{fn.__name__}: {e!r}")

    threads = [threading.Thread(target=run, args=(f,))
               for f in (gf8_work, hh_work, snappy_work, jsonscan_work,
                         syncwave_work)
               for _ in range(3)]
    # one writer thread per drive of a 16-drive set, landing at once
    threads += [threading.Thread(target=run, args=(landing_work,))
                for _ in range(16)]
    for t in threads: t.start()
    for t in threads: t.join()
    assert not errors, errors
    print("TSAN-WORKLOAD-OK")
""")


@tsan
def test_native_libs_clean_under_tsan(tmp_path):
    res = _run_tsan(WORKLOAD, tmp_path)
    assert "TSAN-WORKLOAD-OK" in res.stdout, \
        f"stdout={res.stdout[-2000:]}\nstderr={res.stderr[-4000:]}"
    assert "WARNING: ThreadSanitizer" not in res.stderr, \
        res.stderr[-4000:]
    assert res.returncode == 0, res.stderr[-2000:]


RACE_CANARY_SRC = textwrap.dedent("""
    static long counter = 0;
    extern "C" long mt_race_canary(int n) {
        for (int i = 0; i < n; i++)
            counter = counter + 1;            // unsynchronized RMW
        return counter;
    }
""")

RACE_CANARY_DRIVER = textwrap.dedent("""
    import os, threading
    from minio_tpu.utils import nativelib
    so = os.path.join(os.environ["MT_NATIVE_BUILD_DIR"], "librace.so")
    lib = nativelib.load(os.environ["CANARY_SRC"], so)
    assert lib is not None, "canary build failed"
    def work():
        for _ in range(200):
            lib.mt_race_canary(5000)
    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts: t.start()
    for t in ts: t.join()
    print("RACE-CANARY-DONE")
""")


@tsan
def test_harness_catches_injected_race(tmp_path):
    """The tier is only evidence if it FAILS on a real race.  -O0 keeps
    the per-iteration load/store pair (at -O2 the loop folds into one
    store per call and the race window shrinks below detectability)."""
    src = tmp_path / "race_canary.cc"
    src.write_text(RACE_CANARY_SRC)
    res = _run_tsan(RACE_CANARY_DRIVER, tmp_path, extra_env={
        "MT_NATIVE_CFLAGS": "-fsanitize=thread -O0 -g",
        "CANARY_SRC": str(src),
    })
    assert "WARNING: ThreadSanitizer: data race" in res.stderr, \
        f"injected race was NOT caught\n{res.stderr[-2000:]}"
    assert res.returncode == 66, res.returncode
