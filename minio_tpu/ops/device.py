"""The process's one view of JAX and the accelerator.

Every module of the package that runs device code imports this one
first, so four things are decided exactly once and can be read back
(admin ``info``, ``chip_smoke.py``) instead of being assumed:

  * **the compile cache** — configured below at import, i.e. before any
    jit of the package can run.  ``JAX_COMPILATION_CACHE_DIR`` set:
    JAX reads that directory from the environment itself and this
    module sets none.  Unset: one fixed, git-ignored directory inside
    the checkout (the path is part of the cache key, so it never
    carries a pid, a time or a tmp name) — except in a process pinned
    to the CPU, which gets no cache it did not ask for: a persisted
    XLA:CPU program is of no use to a chip run, and XLA's CPU loader
    logs a multi-KB machine-feature "mismatch" error per entry it
    loads, on the very machine that compiled it.  The one-second
    persistence floor is lowered to zero either way: the Pallas kernels
    compile in 0.2-2 s each and a server recompiles per new object size.
  * **the platform** — ``platform()`` is ``jax.default_backend()``,
    asked once.  An explicit device backend (``tpu``/``mesh``) gets a
    TPU or an explicit CPU opt-in (``JAX_PLATFORMS=cpu``, as tests and
    ``chip_smoke.py --tiny`` do), else ``DeviceUnavailable``;
    ``auto`` means the TPU when there is one and the host codec
    otherwise, with nothing caught on the way.
  * **the kernel form** — ``use_pallas()`` (Pallas program vs the XLA
    formulation) and ``interpret()`` (Mosaic vs the Pallas
    interpreter).  On a TPU both are fixed: Pallas, compiled.  Off it
    the XLA forms run, and ``MT_PALLAS=1`` routes tests through the
    same Pallas programs interpreted.  No call site decides for itself.
  * **what was compiled** — a ``jax.monitoring`` listener counts
    backend compile requests, the seconds spent tracing, lowering and
    compiling, and the persistent-cache hits and writes
    (``compile_stats()``), and keeps the same tally per jitted function
    (``by_function``: which step recompiled).
  * **what crossed the link** — ``upload`` and ``fetch`` are the two
    calls that move bytes between host and device on the codec path;
    each is one ``<op>.upload`` / ``<op>.fetch`` leg (obs/trace.py) and
    counts its array's bytes, padding included, into
    ``mt_tpu_link_bytes_total{op,dir}``.  Importing this module also
    hands obs/trace.py the profiler's ``TraceAnnotation``, so every leg
    lands in a profiler trace on the device's own clock.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..admin.metrics import GLOBAL as _metrics
from ..obs import trace as _trace

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cpu_opt_in() -> bool:
    """CPU is only ever an explicit choice: ``JAX_PLATFORMS`` (or
    ``jax_platforms``) puts it FIRST, which makes it the default
    backend.  ``tpu,cpu`` — what a TPU host may well carry — is not one."""
    return (jax.config.jax_platforms or "").lower().split(",")[0] == "cpu"


def _configure_compile_cache() -> None:
    if not os.environ.get(CACHE_ENV) and not cpu_opt_in():
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


_configure_compile_cache()      # at import: before the package's first jit


class DeviceUnavailable(RuntimeError):
    """An explicit device backend was asked for and there is no TPU
    (and no explicit CPU opt-in)."""


@functools.lru_cache(maxsize=1)
def platform() -> str:
    """The JAX platform of this process.  Initializes the backend: on a
    TPU host the calling process holds the chip from here on."""
    return jax.default_backend()


def resolve_backend(backend: str) -> str:
    """Codec backend name -> the backend that will run.  ``numpy`` is
    the host codec and never touches JAX."""
    if backend == "auto":
        if cpu_opt_in():
            return "numpy"        # pinned to the host: no backend init
        return "tpu" if platform() == "tpu" else "numpy"
    if backend in ("tpu", "mesh") and not cpu_opt_in() \
            and platform() != "tpu":
        raise DeviceUnavailable(
            f"backend {backend!r} needs a TPU but the JAX platform is "
            f"{platform()!r}; set JAX_PLATFORMS=cpu to run the device "
            f"codec on the CPU explicitly, or use --backend numpy")
    return backend


# -- kernel form -------------------------------------------------------------

# cross-compiling for a TPU topology from a host without one
# (tests/test_kernels_compile_v5e.py): the kernels must lower as they
# do on the chip although platform() is "cpu"
_AOT_TPU = False


@contextlib.contextmanager
def aot_tpu():
    """Trace the kernels in their on-chip form regardless of the local
    platform.  Traces are cached by jit, so the caches are dropped on
    both edges."""
    global _AOT_TPU
    jax.clear_caches()
    _AOT_TPU = True
    try:
        yield
    finally:
        _AOT_TPU = False
        jax.clear_caches()


def use_pallas() -> bool:
    """Pallas program (True) or XLA formulation (False)."""
    if _AOT_TPU:
        return True
    env = os.environ.get("MT_PALLAS", "")
    if env in ("0", "1"):
        return env == "1"
    return platform() == "tpu"


def interpret() -> bool:
    """``interpret=`` of every ``pallas_call`` in the package: Mosaic on
    a TPU, the Pallas interpreter anywhere else."""
    return not (_AOT_TPU or platform() == "tpu")


# the programs ``named_jit`` made: ``compile_stats()`` reports what was
# built of each
_NAMED: set = set()


def named_jit(name: str, **jit_kw):
    """``jax.jit`` under a stable program name: the trace's ``XLA
    Modules`` line and ``compile_stats()["by_function"]`` show
    ``jit_<name>`` / ``<name>`` whatever the Python function is called,
    so a reducer finds a kernel after its callers are restructured.

    A program is built once per key (static arguments, operand shapes
    and dtypes) and process: ``jax.jit`` itself parks concurrent first
    callers of one signature on the first one's build (trace, lowering,
    compile or cache retrieval) with the interpreter released, and a
    build that raises sends its waiters on to build for themselves.
    ``compile_stats()`` counts the keys built of each program
    (``builds``).  Nothing stands between a caller and the jitted
    function: a wrapper in front of it, whether it parked the waiters
    on an event of its own, traced in turn under one lock or only
    counted, read ``n16.small-zipf``'s preload 6-9 s longer in sixteen
    runs (PERF.md section 6, PR 35)."""
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        _NAMED.add(name)
        return jax.jit(fn, **jit_kw)
    return wrap


def once_cache(maxsize: int):
    """``functools.lru_cache`` for a factory of programs: concurrent
    first callers of a key get ONE object (the cache alone runs the
    factory in every thread that misses, and each product would trace
    and compile for itself)."""
    def wrap(factory):
        cached = functools.lru_cache(maxsize=maxsize)(factory)
        mu = threading.Lock()

        @functools.wraps(factory)
        def get(*key):
            with mu:
                return cached(*key)
        return get
    return wrap


# -- what was compiled -------------------------------------------------------

_mu = threading.Lock()
_stats = {"compiles": 0, "compile_seconds": 0.0, "trace_seconds": 0.0,
          "lower_seconds": 0.0, "cache_hits": 0, "cache_writes": 0}
# the three stages a new shape pays before it runs.  The persistent
# cache only spares the last: tracing the Pallas HighwayHash (its packet
# chain is unrolled in Python) and lowering it to MLIR are paid again by
# every process, which is most of a warm-cache first PUT.
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "compile_seconds",
}


# per jitted function: [backend compile requests, seconds over the three
# stages].  A server meets a few dozen names (kernels, their wrappers,
# eager one-op programs); past the bound the rest share one row.
_BY_FUNCTION_CAP = 128
_by_function: dict[str, list] = {}


def _on_duration(event: str, secs: float, fun_name: str = "",
                 **_kw) -> None:
    key = _DURATIONS.get(event)
    if key:
        with _mu:
            _stats[key] += secs
            # a "compile" here is a backend compile REQUEST: it covers
            # a persistent-cache retrieval too, so compiles - cache_hits
            # is what the backend really compiled
            _stats["compiles"] += key == "compile_seconds"
            # tracing reports the function's name, lowering its
            # module's (``jit_<name>``), compiling ``jit(<name>)``: one
            # row for the three
            if fun_name.startswith("jit(") and fun_name.endswith(")"):
                fun_name = fun_name[4:-1]
            else:
                fun_name = fun_name.removeprefix("jit_")
            if fun_name not in _by_function and \
                    len(_by_function) >= _BY_FUNCTION_CAP:
                fun_name = "(other)"
            row = _by_function.setdefault(fun_name, [0, 0.0])
            row[0] += key == "compile_seconds"
            row[1] += secs


def _on_event(event: str, **_kw) -> None:
    key = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_writes"}.get(event)
    if key:
        with _mu:
            _stats[key] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_stats() -> dict:
    with _mu:
        out = {k: round(v, 3) for k, v in _stats.items()}
        # a named program's key compiles once (a cache retrieval is a
        # compile request too), so its requests are the keys built
        out["by_function"] = {
            name: {"compiles": n, "seconds": round(secs, 3),
                   "builds": n if name in _NAMED else 0}
            for name, (n, secs) in _by_function.items()}
        out["builds"] = sum(row["builds"]
                            for row in out["by_function"].values())
        return out


# -- what crossed the link ---------------------------------------------------

_trace.set_annotator(jax.profiler.TraceAnnotation)


def _count_link(op: str, direction: str, nbytes: int) -> None:
    _metrics.inc("mt_tpu_link_bytes_total", {"op": op, "dir": direction},
                 float(nbytes))


def upload(op: str, x) -> jax.Array:
    """Host bytes -> a uint8 device array, as the ``<op>.upload`` leg.
    An array already on the device passes through: nothing crosses."""
    if isinstance(x, jax.Array):
        return jnp.asarray(x, jnp.uint8)
    with _trace.span("tpu", op + ".upload",
                     nbytes=getattr(x, "nbytes", 0)):
        out = jnp.asarray(x, jnp.uint8)
    _count_link(op, "h2d", out.nbytes)
    return out


def fetch(op: str, x: jax.Array, rows: int | None = None) -> np.ndarray:
    """A device array (its first ``rows``) -> host memory, as the
    ``<op>.fetch`` leg: the wait for the programs that produce it, then
    the copy down."""
    with _trace.span("tpu", op + ".fetch") as sp:
        out = np.asarray(x if rows is None else x[:rows])
        sp.nbytes = out.nbytes
    _count_link(op, "d2h", out.nbytes)
    return out


def compile_cache() -> dict:
    """Where compiled programs persist and how many are there."""
    path = jax.config.jax_compilation_cache_dir      # None: no cache
    try:
        entries = sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except (OSError, TypeError):
        entries = 0
    return {"dir": path, "from_env": bool(os.environ.get(CACHE_ENV)),
            "entries": entries}


def describe() -> dict:
    """The device as JAX reports it, the kernel form in force, the
    compile tallies, and per-device allocator counters (on a mesh:
    proof that every chip took part).  Initializes the backend."""
    devs = jax.devices()
    per_dev = []
    for d in devs:
        ms = d.memory_stats() or {}
        per_dev.append({"id": d.id,
                        "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                        "num_allocs": ms.get("num_allocs")})
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "kernels": ("xla" if not use_pallas() else
                    "pallas-interpret" if interpret() else "pallas-mosaic"),
        "compile": compile_stats(),
        "compile_cache": compile_cache(),
        "devices": per_dev,
    }
