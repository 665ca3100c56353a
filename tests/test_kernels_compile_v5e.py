"""AOT pre-check: the Pallas kernels and the 1x4 mesh forms must compile
for a TPU v5e with the installed toolchain — from a host without one.

``jax.experimental.topologies`` hands out v5e:2x2 device descriptions,
``ops/device.aot_tpu()`` makes the kernels trace in their on-chip form
(Mosaic, not the interpreter), and ``jit(...).lower(...).compile()``
runs the real Mosaic + XLA:TPU compilers.  Nothing executes, so this
says nothing about bit-identity or speed — ``chip_smoke.py`` on a chip
does — but a kernel PR that Mosaic rejects fails HERE, for free, before
any chip time is spent.  Shapes: 12+4 at the 1 MiB (87382-byte shard)
and 10 MiB (873814-byte shard) block sizes.
"""

import os

os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")   # no metadata server here

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from minio_tpu.ops import (device, gf8, hh_pallas, rs_fused,  # noqa: E402
                           rs_mesh, rs_pallas)

pytestmark = pytest.mark.slow

K, M_PAR, GS = 12, 4, rs_pallas._GS
BLOCK_SIZES = [1 << 20, 10 << 20]


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology API
        pytest.skip(f"TPU topology API unavailable: "
                    f"{type(e).__name__}: {e}")


@pytest.fixture(autouse=True)
def _on_chip_form():
    with device.aot_tpu():
        yield


def _pad(n: int, to: int) -> int:
    return n + (-n) % to


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("bs", BLOCK_SIZES)
def test_single_chip_kernels_compile(topo, bs):
    sh = SingleDeviceSharding(topo.devices[0])
    ss = gf8.shard_size(bs, K)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    # rs_pallas: encode (r=4) and a 2-lost decode (r=2), grouped and
    # the B=1/gs=1 tail form
    n = _pad(ss, rs_pallas._TN)
    for r, B, gs in ((M_PAR, 8, GS), (2, 8, GS), (M_PAR, 1, 1)):
        _compile(lambda a, b, gs=gs: rs_pallas._gf2_apply_bm(
            a, b, gs=gs, tn=rs_pallas._TN),
            spec((gs * 8 * r, gs * 8 * K), jnp.int8),
            spec((B, K, n), jnp.uint8))
    # hh_pallas over a stripe batch's k+m shard rows (UNPADDED width:
    # digests never cover lane padding)
    _compile(hh_pallas.hh256_batch, spec((6 * (K + M_PAR), ss), jnp.uint8))
    # rs_fused: one kernel, D in, P + hash-state planes out
    B = 8
    p = rs_fused.plan(B, K, M_PAR, ss)
    _compile(lambda a, b: rs_fused._fused_call(
        a, b, k=K, ro=M_PAR, gs=p["gs"], bs=p["bs"], S=p["S"],
        pc=p["pc"], n_packets=ss // 32, hash_parity=True),
        spec((p["gs"] * 8 * M_PAR, p["gs"] * 8 * K), jnp.int8),
        spec((p["B_pad"], K, p["n_pad"]), jnp.uint8))
    # the one-chip PUT route's program: kernel + plane reassembly +
    # remainder + finalize, results flat, one stripe per dispatch (the
    # operand arrives staged at the lane tile, nothing pads); also at a
    # width under one 32-byte packet (no packet loop to run)
    for n in (ss, 9):
        p = rs_fused.plan(1, K, M_PAR, n)
        assert p["B_pad"] == 1
        rs_fused._encode_bitrot.lower(
            spec((p["gs"] * 8 * M_PAR, p["gs"] * 8 * K), jnp.int8),
            spec((1, K, p["n_pad"]), jnp.uint8),
            gs=p["gs"], n_real=n).compile()


@pytest.mark.parametrize("k,m,bs", [(8, 4, 1 << 20), (12, 4, 10 << 20),
                                    (2, 2, 10 << 20)],
                         ids=["8+4-1MiB", "12+4-10MiB", "2+2-10MiB"])
def test_stripe_group_program_compiles(topo, k, m, bs):
    """A body of several full blocks: ONE program per stripe group
    (``rs_fused.group_plan``: 10 / 8 / 32 stripes in one 128-lane row),
    and the two small programs that complete a partial group on the
    device and split its results per stripe."""
    sh = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    n = gf8.shard_size(bs, k)
    p = rs_fused.group_plan(k, m, n)
    G = p["bs"]
    assert p["S"] == 1 and G * (k + m) <= 128
    rs_fused._encode_bitrot.lower(
        spec((p["gs"] * 8 * m, p["gs"] * 8 * k), jnp.int8),
        spec((G, k, p["n_pad"]), jnp.uint8),
        gs=p["gs"], n_real=n).compile()
    rs_fused._group_stage.lower(
        *[spec((1, k, p["n_pad"]), jnp.uint8)] * G).compile()
    rs_fused._group_split.lower(
        spec((G * m * p["n_pad"],), jnp.uint8),
        spec((G * (k + m) * 32,), jnp.uint8), G=G).compile()


@pytest.mark.parametrize("bs", BLOCK_SIZES)
def test_mesh_1x4_forms_compile(topo, bs):
    """rs_mesh's shard_map forms on a 1x4 (stripe x shard) mesh: k=12
    split 3 per chip, ppermute ring + all_gather."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("stripe", "shard"))
    S = 4
    kl = K // S
    ss = gf8.shard_size(bs, K)

    def mats(gs):
        return jax.ShapeDtypeStruct(
            (S, gs * 8 * M_PAR, gs * 8 * kl), jnp.int8,
            sharding=NamedSharding(mesh, P("shard", None, None)))

    def data(B, n):
        return jax.ShapeDtypeStruct(
            (B, K, n), jnp.uint8,
            sharding=NamedSharding(mesh, P("stripe", "shard", None)))

    B = 8
    n = _pad(ss, rs_pallas._TN)
    rs_mesh._sharded_apply_pallas(
        mesh, M_PAR, kl, GS, rs_pallas._TN).lower(
        mats(GS), data(B, n)).compile()
    rs_mesh._fused_pallas(
        mesh, M_PAR, kl, GS, rs_pallas._TN, ss).lower(
        mats(GS), data(B, n)).compile()
    p = rs_fused.plan(B, kl, M_PAR, ss, hash_parity=False)
    rs_mesh._fused_pallas_single(
        mesh, M_PAR, kl, p["gs"], p["bs"], p["S"], p["pc"], ss,
        False).lower(
        mats(p["gs"]), data(p["B_pad"], p["n_pad"])).compile()

