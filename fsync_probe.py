#!/usr/bin/env python3
"""Probe how a mount takes concurrent fsyncs, outside the server.

    python3 fsync_probe.py [--dir DIR] [--seconds 6] [--threads 1,16,32,64]
                           [--bytes 873814]

K threads, each in a directory of its own under DIR (default: the system
temp dir, where the benchmark's drive directories live), loop "create a
file of --bytes, fsync it, fsync its directory, unlink it" for --seconds.
One JSON line per K: fsyncs/s over the wall, and the median of the file
fsync, the directory fsync and the create+write, in ms.  A drive's group
commit (storage/commit.py) issues its fsyncs in waves; whether a wave is
worth its hand-offs on a mount is what this says: fsyncs/s that keeps
rising with K means the mount overlaps them.  Imports nothing of the
program; a host measurement, never a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import tempfile
import threading
import time


def _worker(path: str, body: bytes, stop: float, out: list) -> None:
    os.makedirs(path, exist_ok=True)
    creates, fsyncs, dsyncs = [], [], []
    i = 0
    while time.monotonic() < stop:
        name = os.path.join(path, f"f{i}")
        i += 1
        t0 = time.perf_counter()
        fd = os.open(name, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
        try:
            os.write(fd, body)
            t1 = time.perf_counter()
            os.fsync(fd)
            t2 = time.perf_counter()
        finally:
            os.close(fd)
        dfd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
        try:
            t3 = time.perf_counter()
            os.fsync(dfd)
            t4 = time.perf_counter()
        finally:
            os.close(dfd)
        os.unlink(name)
        creates.append(t1 - t0)
        fsyncs.append(t2 - t1)
        dsyncs.append(t4 - t3)
    out.append((creates, fsyncs, dsyncs))


def probe(root: str, k: int, seconds: float, nbytes: int) -> dict:
    body = os.urandom(nbytes)
    out: list = []
    t0 = time.monotonic()
    threads = [threading.Thread(
        target=_worker, args=(os.path.join(root, f"k{k}-t{i}"), body,
                              t0 + seconds, out)) for i in range(k)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    creates = [x for c, _, _ in out for x in c]
    fsyncs = [x for _, f, _ in out for x in f]
    dsyncs = [x for _, _, d in out for x in d]

    def med(xs):
        return round(statistics.median(xs) * 1e3, 3) if xs else None
    return {"threads": k, "bytes": nbytes, "wall_s": round(wall, 2),
            "loops": len(fsyncs),
            "fsyncs_per_s": round((len(fsyncs) + len(dsyncs)) / wall, 1),
            "file_fsync_p50_ms": med(fsyncs),
            "dir_fsync_p50_ms": med(dsyncs),
            "create_write_p50_ms": med(creates)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=tempfile.gettempdir())
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--threads", default="1,16,32,64")
    ap.add_argument("--bytes", type=int, default=873814)
    a = ap.parse_args()
    root = tempfile.mkdtemp(prefix="fsync-probe-", dir=a.dir)
    try:
        for k in (int(x) for x in a.threads.split(",")):
            print(json.dumps(probe(root, k, a.seconds, a.bytes)),
                  flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
