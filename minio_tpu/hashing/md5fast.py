"""Multi-lane native MD5 — the strict-compat ETag engine
(native/md5mb.cc via ctypes; the md5-simd role of the reference's PUT
path, SURVEY §2.4).

Strict S3 compatibility pins the ETag algorithm to MD5, and MD5 is a
serial dependency chain — one stream cannot go faster than one core's
chain latency.  What CAN go faster is *many* streams: concurrent PUTs
and multipart parts each carry an independent digest, and interleaving
their compression rounds in one native call fills the issue slots a
single chain leaves idle.  Three layers here:

  * ``MD5Fast`` — a hashlib-compatible digest object over the native
    single-stream core (ILP-tuned, GIL-free updates so the ETag truly
    runs beside erasure encode and the drive writer queues);
  * ``LaneScheduler`` — a combining scheduler: concurrent ``update``
    calls from different streams coalesce into one N-lane multi-buffer
    native call (``pipeline.md5_lanes`` bounds N, live-reloadable).
    The first caller becomes the combiner and drains the queue; later
    callers park until their chunk is hashed.  With one stream in
    flight the scheduler degenerates to the plain fast core — lanes
    are an opportunistic win, never a wait;
  * graceful fallback — no compiler / ``MT_MD5=hashlib`` / absent
    ``.so`` all land on ``hashlib.md5``; digests are bit-identical
    either way (pinned across lane counts and tail lengths by
    tests/test_md5fast.py).

Counters (doc-linted in docs/observability.md): ``mt_md5_lane_batches_
total{lanes=}`` per combined native call, ``mt_md5_native_bytes_total``
for scheduler-routed bytes, ``mt_md5_fallback_total`` when native was
requested but unavailable.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import threading
import time
from ..utils.locktrace import mtlock

_NATIVE_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native", "md5mb.cc")
_NATIVE_SO = os.path.join(os.path.dirname(_NATIVE_SRC), "build",
                          "libmtmd5.so")

_LIB = None
_LIB_TRIED = False
_STATE_SIZE = 0
_load_lock = mtlock("md5.native-load")


def _get_lib():
    global _LIB, _LIB_TRIED, _STATE_SIZE
    if _LIB_TRIED:
        return _LIB
    with _load_lock:
        if _LIB_TRIED:
            return _LIB
        from ..utils import nativelib
        lib = nativelib.load(_NATIVE_SRC, _NATIVE_SO)
        if lib is not None:
            try:
                lib.mt_md5_state_size.restype = ctypes.c_size_t
                lib.mt_md5_init.argtypes = [ctypes.c_char_p]
                lib.mt_md5_update.argtypes = [
                    ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t]
                lib.mt_md5_final.argtypes = [ctypes.c_char_p,
                                             ctypes.c_char_p]
                lib.mt_md5_oneshot.argtypes = [
                    ctypes.c_void_p, ctypes.c_size_t, ctypes.c_char_p]
                lib.mt_md5mb_update.argtypes = [
                    ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
                    ctypes.POINTER(ctypes.c_void_p),
                    ctypes.POINTER(ctypes.c_size_t)]
                _STATE_SIZE = int(lib.mt_md5_state_size())
            except Exception:  # noqa: BLE001 — fall back to hashlib
                lib = None
        _LIB = lib
        _LIB_TRIED = True
        return _LIB


def _mode() -> str:
    """MT_MD5=hashlib forces the stdlib; MT_MD5=native (the default)
    uses the .so when it loads."""
    return os.environ.get("MT_MD5", "native").strip().lower()


def available() -> bool:
    return _mode() != "hashlib" and _get_lib() is not None


# -- backend ladder ---------------------------------------------------------
#
# ``pipeline.md5_backend`` (kvconfig, live-reloadable through
# reload_pipeline_config) selects the strict-ETag engine:
#
#   device  -> hashing/md5_device.MD5Device: bulk blocks batched onto
#              the accelerator through the md5 combining bucket
#              (parallel/batcher.py); falls to the next rung (counted
#              in mt_md5_device_fallback_total) when no device
#   native  -> MD5Fast over native/md5mb.cc + the host LaneScheduler
#   hashlib -> the stdlib (also forced by MT_MD5=hashlib, which
#              outranks the knob — the operator kill switch)
#   auto    -> MEASURED choice: the device rung only when its probed
#              end-to-end rate (md5_device.device_rate_gibps, transfer
#              included) beats the host core by a margin — the platform
#              name alone says nothing about what H2D plus a serial
#              chain costs against a host core.

_BACKEND = "auto"
_AUTO_CHOICE: str | None = None
_AUTO_DETAIL: dict = {}      # what the probe measured, or why it failed
_AUTO_MARGIN = 1.25


def backend_status() -> dict:
    """The configured rung, what ``auto`` chose (None until its probe
    has landed) and what the probe measured."""
    return {"configured": _BACKEND, "auto_choice": _AUTO_CHOICE,
            "auto_probe": dict(_AUTO_DETAIL),
            "native_available": available()}


def set_backend(name: str) -> None:
    """Install the configured backend (reload_pipeline_config hook);
    unknown names keep the current value.  Changing the backend resets
    the cached auto decision."""
    global _BACKEND, _AUTO_CHOICE
    name = (name or "").strip().lower()
    if name in ("auto", "device", "native", "hashlib") \
            and name != _BACKEND:
        # same-name reloads (every SetConfigKV of an unrelated
        # pipeline knob, every layer construction) must NOT discard a
        # settled measured auto decision — that would thrash strict
        # ETags back to the host rung and respawn probe threads
        _BACKEND = name
        _AUTO_CHOICE = None


def _host_rate_gibps() -> float:
    """One-shot probe of the host single-stream rate (native core when
    present, hashlib otherwise) — the bar the device must clear."""
    import hashlib as _hl
    buf = b"\0" * (1 << 20)
    fn = (lambda: MD5Fast(buf)) if available() else \
        (lambda: _hl.md5(buf))
    fn()                                         # warm
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        fn()
    return reps * len(buf) / (time.perf_counter() - t0) / 2**30


def _resolve_backend() -> str:
    """The effective rung for this digest: env override first, then
    the knob, with ``auto`` resolved (and cached) by measurement."""
    global _AUTO_CHOICE, _AUTO_DETAIL
    env = os.environ.get("MT_MD5")
    env = env.strip().lower() if env is not None else None
    if env == "hashlib":
        return "hashlib"
    be = _BACKEND
    if env in ("device", "native"):              # MT_MD5 pins a rung
        be = env
    if be != "auto":
        return be
    if _AUTO_CHOICE is None:
        from . import md5_device
        if not md5_device.available():
            _AUTO_CHOICE = "native"
            _AUTO_DETAIL = {"error": md5_device.unavailable_reason()}
        else:
            # probe OFF the request path: device_rate_gibps pays an
            # XLA compile plus ~20 MiB of benchmark hashing — charged
            # to a background thread, not to the first strict PUT of
            # the process.  Until the probe lands, auto serves the
            # host rung (always correct, never slower than today).
            _start_auto_probe()
            return "native"
    return _AUTO_CHOICE


_probe_lock = mtlock("md5.auto-probe")
_probe_started = False


def _start_auto_probe() -> None:
    global _probe_started
    with _probe_lock:
        if _probe_started:
            return
        _probe_started = True

    def probe():
        global _AUTO_CHOICE, _AUTO_DETAIL, _probe_started
        try:
            from . import md5_device
            dev = md5_device.device_rate_gibps()
            host = _host_rate_gibps()
            choice = "device" if dev > host * _AUTO_MARGIN \
                else "native"
            detail = {"device_gibps": round(dev, 4),
                      "host_gibps": round(host, 4)}
        except Exception as e:  # noqa: BLE001 — a broken probe means host; backend_status() says why
            choice = "native"
            detail = {"error": f"{type(e).__name__}: {e}"}
        with _probe_lock:
            if _AUTO_CHOICE is None:
                _AUTO_CHOICE, _AUTO_DETAIL = choice, detail
            _probe_started = False

    threading.Thread(target=probe, daemon=True,
                     name="mt-md5-calibrate").start()


def _buf_addr(data) -> tuple[int, int, object]:
    """(address, length, keepalive) for any contiguous buffer without
    copying (bytes, bytearray, memoryview slices, numpy rows)."""
    if isinstance(data, bytes):
        return (ctypes.cast(ctypes.c_char_p(data), ctypes.c_void_p).value
                or 0, len(data), data)
    import numpy as np
    arr = data if isinstance(data, np.ndarray) \
        else np.frombuffer(data, dtype=np.uint8)
    if not arr.flags["C_CONTIGUOUS"]:
        arr = np.ascontiguousarray(arr)
    return arr.ctypes.data, arr.size, arr


class MD5Fast:
    """hashlib.md5-compatible object over the native core.  ``digest``
    finalizes a copy of the state, so the stream stays usable (the
    same contract as the stdlib)."""

    name = "md5"
    digest_size = 16
    block_size = 64

    __slots__ = ("_st", "_lib")

    def __init__(self, data=b""):
        self._lib = _get_lib()
        self._st = ctypes.create_string_buffer(_STATE_SIZE)
        self._lib.mt_md5_init(self._st)
        if data:
            self.update(data)

    def update(self, data) -> None:
        addr, n, _keep = _buf_addr(data)
        if n:
            self._lib.mt_md5_update(self._st, addr, n)

    def digest(self) -> bytes:
        cp = ctypes.create_string_buffer(self._st.raw, _STATE_SIZE + 1)
        out = ctypes.create_string_buffer(16)
        self._lib.mt_md5_final(cp, out)
        return out.raw[:16]

    def hexdigest(self) -> str:
        return self.digest().hex()

    def copy(self) -> "MD5Fast":
        c = MD5Fast.__new__(MD5Fast)
        c._lib = self._lib
        c._st = ctypes.create_string_buffer(self._st.raw, _STATE_SIZE + 1)
        return c


def md5(data=b""):
    """Digest factory for the ETag hot path, walking the backend
    ladder (see ``set_backend``): device -> native -> hashlib, each
    rung falling through with its fallback counted."""
    be = _resolve_backend()
    if be == "device":
        from . import md5_device
        if md5_device.available():
            return md5_device.MD5Device(data)
        from ..admin.metrics import GLOBAL as _mtr
        _mtr.inc("mt_md5_device_fallback_total")
    if be != "hashlib" and available():
        return MD5Fast(data)
    if be != "hashlib":
        from ..admin.metrics import GLOBAL as _mtr
        _mtr.inc("mt_md5_fallback_total")
    return hashlib.md5(bytes(data) if not isinstance(
        data, (bytes, bytearray, memoryview)) else data)


class LaneScheduler:
    """Combining N-lane scheduler: concurrent streams' chunk updates
    coalesce into one multi-buffer native call.

    The first thread to arrive becomes the combiner; it drains the
    pending queue in batches of up to ``lanes`` and hashes each batch
    with ONE GIL-free ``mt_md5mb_update``.  Later arrivals park on an
    event until their chunk is done (their pool thread yields the core
    to encode/writers meanwhile).  A stream's own updates are ordered
    by its caller (the _md5_link chain waits on the previous link), so
    a given digest never appears twice in one batch."""

    def __init__(self, lanes: int | None = None):
        self._mu = mtlock("md5.sched")
        self._q: list[list] = []        # [h, chunk, event, exc]
        self._combining = False
        self._lanes = lanes

    def lanes(self) -> int:
        if self._lanes is None:
            try:
                from ..utils.kvconfig import Config
                self._lanes = max(1, int(Config().get("pipeline",
                                                      "md5_lanes")))
            except Exception:  # noqa: BLE001 — default below
                self._lanes = 4
        return self._lanes

    def set_lanes(self, n: int) -> None:
        self._lanes = max(1, int(n))

    def update(self, h, chunk) -> None:
        """Hash ``chunk`` into ``h``, sharing lanes with whatever other
        streams are updating right now.  Falls through to a plain
        update for hashlib objects (native absent) and when lanes are
        disabled."""
        if not isinstance(h, MD5Fast) or self.lanes() <= 1:
            h.update(chunk)
            return
        item = [h, chunk, threading.Event(), None]
        with self._mu:
            self._q.append(item)
            lead = not self._combining
            if lead:
                self._combining = True
        if not lead:
            item[2].wait()
            if item[3] is not None:
                raise item[3]
            return
        # combiner: drain the queue (our own item included), then
        # release the role so the next arrival leads a new round.  The
        # combiner's OWN chunk rides one of the batches below — its
        # exc slot must be re-checked on the way out exactly like a
        # parked caller's, else a failed batch would silently skip
        # this stream's chunk and serve a wrong ETag.
        try:
            while True:
                with self._mu:
                    batch = self._q[:self.lanes()]
                    del self._q[:len(batch)]
                    if not batch:
                        self._combining = False
                        break
                lanes = self.lanes()
                if len(batch) < lanes:
                    # GIL yields before an under-full round: streams
                    # woken by the previous round's events are runnable
                    # but unscheduled, and without the yields a fresh
                    # combiner races ahead with 1-lane rounds forever
                    # (measured: alternating 1/3-lane batches instead
                    # of steady 4-lane).  A yield is not a wait — a
                    # genuinely lone stream pays a few no-op syscalls
                    # (~µs) per ~1 MiB slice (~ms).
                    for _ in range(lanes - len(batch)):
                        time.sleep(0)
                        with self._mu:
                            extra = self._q[:lanes - len(batch)]
                            del self._q[:len(extra)]
                        batch = batch + extra
                        if len(batch) >= lanes:
                            break
                self._run_batch(batch)
        except BaseException:
            with self._mu:
                self._combining = False
            raise
        if item[3] is not None:
            raise item[3]

    def _run_batch(self, batch: list[list]) -> None:
        from ..admin.metrics import GLOBAL as _mtr
        n = len(batch)
        try:
            if n == 1:
                h, chunk, _, _ = batch[0]
                h.update(chunk)
                nbytes = len(memoryview(chunk).cast("B")) \
                    if not isinstance(chunk, bytes) else len(chunk)
            else:
                lib = _get_lib()
                states = (ctypes.c_void_p * n)()
                ptrs = (ctypes.c_void_p * n)()
                lens = (ctypes.c_size_t * n)()
                keep = []
                for i, it in enumerate(batch):
                    states[i] = ctypes.addressof(it[0]._st)
                    addr, ln, ka = _buf_addr(it[1])
                    ptrs[i] = addr
                    lens[i] = ln
                    keep.append(ka)
                lib.mt_md5mb_update(n, states, ptrs, lens)
                nbytes = sum(lens[i] for i in range(n))
            _mtr.inc("mt_md5_lane_batches_total", {"lanes": str(n)})
            _mtr.inc("mt_md5_native_bytes_total", value=float(nbytes))
        except Exception as e:  # noqa: BLE001 — surface on each caller
            for it in batch:
                it[3] = e
        finally:
            for it in batch:
                it[2].set()


SCHED = LaneScheduler()

# scheduler-routed oneshot slice size: big enough that per-call
# overhead vanishes, small enough that two concurrent 4 MiB oneshots
# interleave across many batches instead of missing each other
ONESHOT_SLICE = 1 << 20


def md5_of(data):
    """Whole-buffer digest routed through the lane scheduler in
    ONESHOT_SLICE steps, so concurrent single-part PUTs' ETag passes
    share lanes (the overlapped bytes-PUT path submits this on the
    pool).  Returns the digest object (hexdigest() for the ETag)."""
    h = md5()
    if type(h).__name__ == "MD5Device":
        # device digests combine through the md5 bucket instead of the
        # host lane scheduler; slicing still interleaves concurrent
        # oneshots across batched dispatches
        mv = memoryview(data).cast("B")
        for off in range(0, len(mv), ONESHOT_SLICE):
            h.update(mv[off:off + ONESHOT_SLICE])
        return h
    if not isinstance(h, MD5Fast):
        h.update(bytes(data) if not isinstance(
            data, (bytes, bytearray, memoryview)) else data)
        return h
    mv = memoryview(data).cast("B")
    for off in range(0, len(mv), ONESHOT_SLICE):
        SCHED.update(h, mv[off:off + ONESHOT_SLICE])
    return h
