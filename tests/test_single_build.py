"""A program is built once per shape and process (ISSUE 35): of the
threads that meet a ``device.named_jit`` program's key (static
arguments, operand shapes and dtypes) for the first time together, one
builds it and the others wait for that build with the interpreter
released, then run the compiled program — ``jax.jit``'s own wait, which
these tests pin because the set-up time of a server rests on it.
``compile_stats()`` counts the keys built of each named program
(``builds``).
"""

import threading
import time

import numpy as np
import pytest

from minio_tpu.ops import device

_SEQ = [0]


def _program(body_calls: list, trace_s: float = 0.3, fail: list = ()):
    """A fresh ``named_jit`` program whose trace takes ``trace_s`` (long
    enough for the other threads to arrive), notes every entry of its
    body, and raises while ``fail`` is non-empty."""
    _SEQ[0] += 1
    name = f"mt_test_single_build_{_SEQ[0]}"

    @device.named_jit(name, static_argnames=("scale",))
    def prog(x, *, scale):
        body_calls.append(threading.get_ident())
        time.sleep(trace_s)
        if fail:
            fail.pop()
            raise RuntimeError("the build failed")
        return x * scale + 1

    return name, prog


def _builds(name: str) -> int:
    return device.compile_stats()["by_function"].get(
        name, {"builds": 0})["builds"]


def _together(n: int, fn, timeout: float = 60.0) -> list:
    """``fn(i)`` on n threads behind one barrier; a thread that has not
    returned after ``timeout`` fails the test (a waiter left hanging)."""
    got: list = [None] * n
    gate = threading.Barrier(n)

    def run(i):
        gate.wait(10)
        try:
            got[i] = fn(i)
        except Exception as e:  # noqa: BLE001 — handed to the test's thread
            got[i] = e

    ths = [threading.Thread(target=run, args=(i,), name=f"mt-test-b{i}",
                            daemon=True) for i in range(n)]
    for t in ths:
        t.start()
    deadline = time.monotonic() + timeout
    for t in ths:
        t.join(max(0.1, deadline - time.monotonic()))
        assert not t.is_alive(), f"{t.name} hangs"
    return got


def test_sixteen_threads_meet_a_new_shape_and_it_is_built_once():
    body: list = []
    name, prog = _program(body)
    x = np.arange(64, dtype=np.int32).reshape(8, 8)
    total = device.compile_stats()["builds"]
    got = _together(16, lambda i: np.asarray(prog(x, scale=3)))
    assert len(body) == 1, "the function body was entered once"
    assert _builds(name) == 1
    for out in got:
        assert np.array_equal(out, x * 3 + 1)
    assert device.compile_stats()["builds"] - total == 1
    prog(x, scale=3)                     # a built key builds nothing
    assert len(body) == 1 and _builds(name) == 1


def test_two_shapes_from_two_groups_of_threads_build_once_each():
    body: list = []
    name, prog = _program(body)
    xs = [np.ones((4, 4), np.int32), np.ones((4, 8), np.int32)]
    got = _together(12, lambda i: np.asarray(prog(xs[i % 2], scale=2)))
    assert len(body) == 2
    assert _builds(name) == 2
    for i, out in enumerate(got):
        assert out.shape == xs[i % 2].shape and (out == 3).all()
    # a static argument is part of the key: another value, another build
    assert (np.asarray(prog(xs[0], scale=5)) == 6).all()
    assert len(body) == 3 and _builds(name) == 3


def test_a_build_that_raises_leaves_no_waiter_hanging():
    """The builder's error is its own; every waiter is released and
    builds for itself (here they succeed, so the waiters all get
    results), and a shape whose build failed is built by the next
    call."""
    body: list = []
    fail = [1]
    name, prog = _program(body, fail=fail)
    x = np.ones((2, 2), np.int32)
    got = _together(8, lambda i: np.asarray(prog(x, scale=7)), timeout=30)
    errors = [g for g in got if isinstance(g, Exception)]
    assert len(errors) == 1 and "the build failed" in str(errors[0])
    assert all((g == 8).all() for g in got if not isinstance(g, Exception))
    assert len(body) >= 2 and _builds(name) >= 1
    fail.append(1)
    y = np.ones((3, 3), np.int32)
    before = _builds(name)
    with pytest.raises(RuntimeError, match="the build failed"):
        prog(y, scale=7)
    assert _builds(name) == before
    assert (np.asarray(prog(y, scale=7)) == 8).all()
    assert _builds(name) == before + 1


def test_a_call_under_an_outer_trace_is_part_of_that_programs_build():
    """``mt_rs_fused`` inside ``mt_encode_bitrot``, ``mt_hh256_batch``
    inside the mesh program: the inner call builds nothing of its
    own."""
    body: list = []
    name, inner = _program(body, trace_s=0.0)

    @device.named_jit(name + "_outer")
    def outer(x):
        return inner(x, scale=2) + 1

    assert (np.asarray(outer(np.ones((2, 2), np.int32))) == 4).all()
    assert _builds(name) == 0
    assert _builds(name + "_outer") == 1


def test_builds_count_named_programs_only():
    import jax.numpy as jnp
    np.asarray(jnp.flip(jnp.arange(7)))     # some eager one-op program
    rows = device.compile_stats()["by_function"]
    assert all(row["builds"] == 0 for name, row in rows.items()
               if not name.startswith("mt_"))


def test_a_factory_of_programs_hands_concurrent_callers_one_object():
    made = []

    @device.once_cache(8)
    def factory(key):
        time.sleep(0.05)
        made.append(key)
        return object()

    got = _together(8, lambda i: factory("a"))
    assert made == ["a"] and len({id(g) for g in got}) == 1
    assert factory("b") is not got[0] and made == ["a", "b"]
