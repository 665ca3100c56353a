"""Nothing hides the device (ISSUE 21): a device-kernel failure fails the
request instead of succeeding through the host, an explicit device
backend without a TPU and without the explicit CPU opt-in refuses to
start, and the compile cache sits where JAX_COMPILATION_CACHE_DIR says
(or at the one fixed checkout path), set nowhere else.
"""

import os

import jax
import numpy as np
import pytest

from minio_tpu import server_main
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.ops import device, hh_kernels, hh_pallas, rs_mesh
from minio_tpu.storage.xl_storage import XLStorage


class Induced(RuntimeError):
    pass


def _boom(*_a, **_kw):
    raise Induced("induced device-kernel failure")


def test_device_hash_failure_fails_the_put(tmp_path, monkeypatch):
    """The device bitrot hash raising must surface as a failed PUT: the
    host C HighwayHash used to step in silently (bitrot.py fallback)."""
    disks = []
    for i in range(4):
        (tmp_path / f"d{i}").mkdir()
        disks.append(XLStorage(str(tmp_path / f"d{i}")))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="tpu", inline_threshold=0)
    layer.make_bucket("bkt")
    # whichever form ops/device.py picks on this platform
    monkeypatch.setattr(hh_pallas, "hh256_batch", _boom)
    monkeypatch.setattr(hh_kernels, "hh256_batch", _boom)
    with pytest.raises(Induced):
        layer.put_object("bkt", "obj", b"x" * 100_000)
    with pytest.raises(Exception):
        layer.get_object("bkt", "obj")


def test_fused_single_kernel_failure_raises(monkeypatch):
    """The single fused kernel failing must raise, not be memoized into
    the two-kernel pipeline (rs_mesh's old _SINGLE_STATE fallback)."""
    monkeypatch.setenv("MT_PALLAS", "1")
    monkeypatch.delenv("MT_FUSED_SINGLE", raising=False)
    monkeypatch.setattr(rs_mesh, "_encode_with_bitrot_single", _boom)
    two_kernel = []
    monkeypatch.setattr(rs_mesh, "_encode_with_bitrot_pallas",
                        lambda *a: two_kernel.append(a))
    blocks = np.zeros((1, 4, 256), np.uint8)
    for _ in range(2):                       # no memoized switch either
        with pytest.raises(Induced):
            rs_mesh.encode_with_bitrot(4, 2, blocks)
    assert not two_kernel


def test_explicit_device_backend_needs_tpu_or_cpu_opt_in(monkeypatch,
                                                         capsys):
    """tests run with JAX_PLATFORMS=cpu — the opt-in; without it an
    explicit tpu/mesh backend on a non-TPU platform is an error, and
    the server entry point exits non-zero naming the reason."""
    assert device.cpu_opt_in()
    assert device.resolve_backend("tpu") == "tpu"
    assert device.resolve_backend("auto") == "numpy"
    monkeypatch.setattr(device, "cpu_opt_in", lambda: False)
    monkeypatch.setattr(device, "platform", lambda: "cpu")
    for backend in ("tpu", "mesh"):
        with pytest.raises(device.DeviceUnavailable):
            device.resolve_backend(backend)
    assert device.resolve_backend("auto") == "numpy"
    assert device.resolve_backend("numpy") == "numpy"
    rc = server_main.main(["server", "/nonexistent/d1", "--backend", "tpu"])
    assert rc == 2
    assert "needs a TPU" in capsys.readouterr().err
    assert not os.path.exists("/nonexistent/d1")
    monkeypatch.setattr(device, "platform", lambda: "tpu")
    assert device.resolve_backend("auto") == "tpu"
    assert device.resolve_backend("mesh") == "mesh"


def test_compile_cache_dir_honours_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: that directory, read by JAX from
    the environment, and no directory set in code.  Unset: the one
    fixed path inside the checkout — except in a process pinned to the
    CPU (this one), which gets no cache it did not ask for."""
    want = os.environ.get(device.CACHE_ENV) or None
    assert jax.config.jax_compilation_cache_dir == want
    assert device.compile_cache()["dir"] == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert device.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))

    def configured(env, cpu):
        updates.clear()
        if env:
            monkeypatch.setenv(device.CACHE_ENV, env)
        else:
            monkeypatch.delenv(device.CACHE_ENV, raising=False)
        monkeypatch.setattr(device, "cpu_opt_in", lambda: cpu)
        device._configure_compile_cache()
        return dict(updates).get("jax_compilation_cache_dir")

    assert configured("/some/where", cpu=False) is None
    assert configured("/some/where", cpu=True) is None
    assert configured("", cpu=True) is None
    assert configured("", cpu=False) == device.DEFAULT_CACHE_DIR


def test_compile_stats_say_which_function_compiled():
    """``by_function`` (admin ``info`` -> ``codec.device.compile``): the
    compile listener tallies backend compile requests and seconds per
    jitted function, under the stable name ``named_jit`` gives."""
    @device.named_jit("mt_test_probe_23", static_argnames=("n",))
    def whatever_it_is_called(x, n):
        return x * n + 1

    before = device.compile_stats()
    assert "mt_test_probe_23" not in before["by_function"]
    whatever_it_is_called(np.arange(7, dtype=np.int32), n=3)
    whatever_it_is_called(np.arange(7, dtype=np.int32), n=3)   # cached
    whatever_it_is_called(np.arange(9, dtype=np.int32), n=3)   # new shape
    after = device.compile_stats()
    row = after["by_function"]["mt_test_probe_23"]
    assert row["compiles"] == 2 and row["seconds"] > 0, row
    assert after["compiles"] - before["compiles"] >= 2
    # the per-function rows add up to the process totals
    assert sum(r["compiles"] for r in after["by_function"].values()) \
        == after["compiles"]


def test_kernel_programs_carry_stable_names():
    """The Pallas kernels' jitted wrappers are ``jit_mt_rs_gf2`` /
    ``jit_mt_hh256`` in a trace and in ``by_function`` (they were
    ``_gf2_apply_bm`` / ``_run_nat``), whatever the callers become."""
    from minio_tpu.ops import rs_pallas
    assert rs_pallas._gf2_apply_bm.__name__ == "mt_rs_gf2"
    assert hh_pallas._run_nat.__name__ == "mt_hh256"
    lowered = rs_pallas._gf2_apply_bm.lower(
        jax.ShapeDtypeStruct((32, 16), np.int8),
        jax.ShapeDtypeStruct((1, 2, 128), np.uint8), gs=1, tn=128)
    assert "jit_mt_rs_gf2" in lowered.as_text()
