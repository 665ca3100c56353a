"""The shim a benchmark run starts its servers under.

    python benchmarks/harness/serve.py <control dir> <argv of minio_tpu ...>

It calls the program's own entry point, ``minio_tpu.server_main.main``,
on the main thread with exactly the argv given.  Only the process that
holds a chip can trace it and the program has no profiler hook, so one
side thread here starts and stops ``jax.profiler`` when the harness asks
through files in the control directory:

    trace.start   (harness writes)  {"seconds": s}: start_trace into
                                    <control dir>/trace, stop_trace s later
    trace.done    (shim writes)     {"t_start", "t_stop"} on CLOCK_MONOTONIC
                                    and {"unix_ns_start", "unix_ns_stop"},
                                    the slice between the two calls; or
                                    {"error": ...}

Without a control directory ("-") there is no side thread.  Nothing runs
in the request path either way.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _wait_for(path: str, stop: threading.Event) -> bool:
    while not stop.is_set():
        if os.path.exists(path):
            return True
        time.sleep(0.05)
    return False


def trace_on_request(ctl: str, stop: threading.Event) -> None:
    if not _wait_for(os.path.join(ctl, "trace.start"), stop):
        return
    out: dict = {}
    try:
        with open(os.path.join(ctl, "trace.start")) as f:
            seconds = json.load(f)["seconds"]
        import jax
        opts = jax.profiler.ProfileOptions()
        # the device planes are what the reducer needs; the Python
        # tracer would record every bytecode call of a 20-thread server
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(os.path.join(ctl, "trace"),
                                 profiler_options=opts)
        out["t_start"], out["unix_ns_start"] = time.monotonic(), time.time_ns()
        stop.wait(seconds)
        out["t_stop"], out["unix_ns_stop"] = time.monotonic(), time.time_ns()
        jax.profiler.stop_trace()
        out["t_written"] = time.monotonic()
    except Exception as e:  # noqa: BLE001 — reported to the harness, which fails the run
        out["error"] = f"{type(e).__name__}: {e}"
    tmp = os.path.join(ctl, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(ctl, "trace.done"))


def main(argv: list[str]) -> int:
    ctl, server_argv = argv[0], argv[1:]
    sys.path.insert(0, ROOT)
    from minio_tpu.server_main import main as server_main
    stop = threading.Event()
    if ctl != "-":
        threading.Thread(target=trace_on_request, args=(ctl, stop),
                         daemon=True, name="bench-profiler").start()
    try:
        return server_main(server_argv)
    finally:
        stop.set()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
