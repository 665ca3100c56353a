"""Start, read back and stop the deployment a configuration file names,
and fail the drives it names as down.

``start_servers`` / ``stop_servers`` / ``wait_live`` / ``scrape`` follow
``chip_smoke.py`` (the floor under every cell); here the argv and the
child environment come from ``configs/<name>.json`` and every child runs
under ``serve.py``.  The harness never imports JAX: what is said about
the device is read back from the servers' admin ``info``.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import socket
import subprocess
import sys
import time

from .client import S3Conn

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEY, SECRET = "minioadmin", "minioadmin"
ADMIN = "/minio-tpu/admin/v1"


class Failed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Deployment:
    def __init__(self, config: dict, work: str, trace: bool,
                 rehearse: bool):
        self.config, self.work, self.trace = config, work, trace
        self.rehearse = rehearse
        self.dirs = [os.path.join(work, f"d{i:02d}")
                     for i in range(config["drives"])]
        self.procs: list[subprocess.Popen] = []
        self.endpoints: list[str] = []
        self.logs: list[str] = []
        self.ctls: list[str] = []

    # -- start / stop ----------------------------------------------------

    def start(self) -> None:
        specs = self.config["processes"]
        s3 = [f"127.0.0.1:{free_port()}" for _ in specs]
        rpc = [free_port() for _ in specs]
        peers = [f"{p['id']}=127.0.0.1:{rpc[i]}="
                 + ",".join(self.dirs[d] for d in p["drives"])
                 for i, p in enumerate(specs)]
        for i, p in enumerate(specs):
            argv: list[str] = []
            for a in p["argv"]:
                if a == "{drives}":
                    argv += [self.dirs[d] for d in p["drives"]]
                elif a == "{peers}":
                    argv += peers
                else:
                    argv.append(a.replace("{s3}", s3[i]))
            if self.rehearse:
                # a CPU-pinned `auto` resolves to the host codec; the
                # rehearsal must walk the device path's code and counters
                argv = ["tpu" if a == "auto" else a for a in argv]
            ctl = "-"
            if self.trace:
                ctl = os.path.join(self.work, f"ctl-{p['id']}")
                os.makedirs(ctl)
            self.ctls.append(ctl)
            log = os.path.join(self.work, f"{p['id']}.log")
            self.logs.append(log)
            env = dict(os.environ, **p.get("env", {}))
            with open(log, "wb") as out:
                self.procs.append(subprocess.Popen(
                    [sys.executable, os.path.join(HERE, "serve.py"), ctl,
                     *argv], cwd=ROOT, env=env, stdout=out,
                    stderr=subprocess.STDOUT))
            self.endpoints.append(f"http://{s3[i]}")

    def stop(self) -> None:
        """Every process started here is gone when this returns: the
        admin stop first (a clean exit), then SIGTERM, then SIGKILL."""
        for p, ep in zip(self.procs, self.endpoints):
            if p.poll() is None:
                try:
                    c = S3Conn(ep, KEY, SECRET, timeout=10)
                    c.request("POST", f"{ADMIN}/service",
                              query="action=stop")
                    c.close()
                except (OSError, http.client.HTTPException):
                    pass                    # terminate() below covers it
        for step in ("wait", "terminate", "kill"):
            deadline = time.monotonic() + 15
            for p in self.procs:
                if p.poll() is None and step != "wait":
                    getattr(p, step)()
            for p in self.procs:
                try:
                    p.wait(max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    pass

    def fail_drives(self) -> list[int]:
        """The configuration's ``down_drives`` lose their drive, from
        outside the servers: ``dNN`` is renamed to ``dNN.failed`` and an
        empty regular file takes its path, so that every open, stat or
        mkdir below it fails (ENOTDIR), as on a drive whose mount is gone.
        Not an empty directory: the servers would format and heal into
        it, which is a replaced drive, another deployment."""
        down = list(self.config.get("down_drives", []))
        for i in down:
            path = self.dirs[i]
            os.rename(path, path + ".failed")
            with open(path, "xb"):
                pass
        return down

    def wait_live(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        pending = list(self.endpoints)
        while time.monotonic() < deadline:
            for p in self.procs:
                check(p.poll() is None, f"server process exited with code "
                      f"{p.returncode} before serving")
            try:
                c = http.client.HTTPConnection(
                    pending[0].split("//")[1], timeout=5)
                c.request("GET", "/minio/health/live")
                ok = c.getresponse().status == 200
                c.close()
                if ok:
                    pending.pop(0)
                    if not pending:
                        return
                    continue
            except OSError:
                pass
            time.sleep(0.25)
        raise Failed(f"{pending[0]} not live after {timeout:.0f}s")

    def log_tails(self, n: int = 2500) -> dict:
        out = {}
        for path in self.logs:
            try:
                with open(path, "rb") as f:
                    out[os.path.basename(path)] = \
                        f.read()[-n:].decode(errors="replace")
            except OSError:
                pass
        return out

    # -- reading the servers back ---------------------------------------------

    def info(self) -> list[dict]:
        """admin ``info`` of every process."""
        out = []
        for ep in self.endpoints:
            c = S3Conn(ep, KEY, SECRET, timeout=120)
            st, _, body, _, _ = c.request("GET", f"{ADMIN}/info")
            c.close()
            check(st == 200, f"admin info at {ep}: HTTP {st}")
            out.append(json.loads(body))
        return out

    def scrape(self) -> dict:
        """{family: [(labels dict, value)]} summed over processes by
        simple concatenation: counters of different processes add."""
        out: dict = {}
        for ep in self.endpoints:
            c = http.client.HTTPConnection(ep.split("//")[1], timeout=60)
            c.request("GET", "/minio-tpu/metrics")
            text = c.getresponse().read().decode()
            c.close()
            for fam, labels, val in parse_scrape(text):
                out.setdefault(fam, []).append((labels, val))
        return out


_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_scrape(text: str):
    """Prometheus text format -> (family, {label: value}, float)."""
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            val = float(m.group(3))
        except ValueError:
            continue
        yield m.group(1), dict(_LABEL.findall(m.group(2) or "")), val
