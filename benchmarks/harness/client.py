"""The benchmark's own S3 client and load generator.

``S3Conn`` is one keep-alive HTTP connection that signs with SigV4 from a
payload hash made beforehand.  Run as a script it is one generator
process: it reads a JSON spec on its first stdin line, then commands
(``connect``, ``preload``, ``start``, ``stop``), one per line, and answers
each with one JSON line on stdout.  Every thread is a closed loop: its next
request leaves when the last reply has been read in full.

Connections are opened in set-up, ONE AT A TIME over all generator
processes, each proven by a HEAD round trip (``connect``).  The program's
S3 front listens with the stdlib's backlog of 5
(``socketserver.TCPServer.request_queue_size``): twenty clients that
connect in the same instant overflow it, the kernel answers the rest with
SYN cookies, and a cookie that fails to validate resets the client's first
request (``ConnectionResetError`` on about one small-object run in eight on
the chip machine; PERF.md section 6).  Nothing is retried once a
connection is proven: a request that fails is a failed operation.

Latency is taken from just before the request's first byte is sent to
just after the reply's last byte is read, on CLOCK_MONOTONIC.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import json
import os
import select
import sys
import threading
import time
import urllib.parse

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
from benchmarks.harness.traffic import BodyPool, OpStream  # noqa: E402

EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()
REGION, SERVICE = "us-east-1", "s3"
OPEN_TRIES = 3


class S3Conn:
    """One persistent connection to one endpoint."""

    def __init__(self, endpoint: str, access_key: str, secret_key: str,
                 timeout: float = 600.0):
        self.host = endpoint.split("//", 1)[1]
        self.ak, self.sk = access_key, secret_key
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None
        self._key: tuple[str, bytes] | None = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def open(self, path: str, tries: int = OPEN_TRIES) -> int:
        """Have a live connection, proven by one HEAD round trip on
        ``path`` (any status proves it).  Set-up only.  An attempt can
        still fail: the server's idle close can fall between ``request``'s
        look at the socket and its send.  Returns how many attempts failed
        first; the last failure is raised."""
        failed = 0
        while True:
            try:
                self.request("HEAD", path)
                return failed
            except (OSError, http.client.HTTPException):
                failed += 1
                if failed == tries:
                    raise
                time.sleep(0.2 * failed)

    def _signing_key(self, date: str) -> bytes:
        if self._key is None or self._key[0] != date:
            k = ("AWS4" + self.sk).encode()
            for part in (date, REGION, SERVICE, "aws4_request"):
                k = hmac.new(k, part.encode(), hashlib.sha256).digest()
            self._key = (date, k)
        return self._key[1]

    def headers(self, method: str, path: str, query: str,
                payload_sha256: str) -> dict:
        amz = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y%m%dT%H%M%SZ")
        cq = "&".join(sorted(
            f"{urllib.parse.quote(k, safe='-._~')}="
            f"{urllib.parse.quote(v, safe='-._~')}"
            for k, v in urllib.parse.parse_qsl(query,
                                               keep_blank_values=True)))
        canon = "\n".join([
            method, urllib.parse.quote(path, safe="/-._~"), cq,
            f"host:{self.host}\nx-amz-content-sha256:{payload_sha256}\n"
            f"x-amz-date:{amz}\n",
            "host;x-amz-content-sha256;x-amz-date", payload_sha256])
        scope = f"{amz[:8]}/{REGION}/{SERVICE}/aws4_request"
        sts = "\n".join(["AWS4-HMAC-SHA256", amz, scope,
                         hashlib.sha256(canon.encode()).hexdigest()])
        sig = hmac.new(self._signing_key(amz[:8]), sts.encode(),
                       hashlib.sha256).hexdigest()
        return {"Host": self.host, "x-amz-date": amz,
                "x-amz-content-sha256": payload_sha256,
                "Authorization":
                    f"AWS4-HMAC-SHA256 Credential={self.ak}/{scope}, "
                    f"SignedHeaders=host;x-amz-content-sha256;x-amz-date, "
                    f"Signature={sig}"}

    def request(self, method: str, path: str, body: bytes = b"",
                payload_sha256: str = EMPTY_SHA256, query: str = "",
                into: bytearray | None = None):
        """-> (status, lowercase headers, body bytes or the count of bytes
        read into ``into``, t_start, t_end).  Raises OSError /
        http.client.HTTPException; the connection is dropped then and
        opened again by the next call."""
        hdrs = self.headers(method, path, query, payload_sha256)
        if self._conn is not None and (
                self._conn.sock is None
                or select.select([self._conn.sock], [], [], 0)[0]):
            # readable with no request out: the server has closed it (its
            # keep-alive idle limit is 30 s); sending would be reset
            self.close()
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, timeout=self.timeout)
            self._conn.connect()
        url = urllib.parse.quote(path, safe="/-._~") \
            + (f"?{query}" if query else "")
        try:
            t0 = time.monotonic()
            self._conn.request(method, url, body=body, headers=hdrs)
            resp = self._conn.getresponse()
            if into is not None and resp.status == 200:
                view, n = memoryview(into), 0
                while True:
                    got = resp.readinto(view[n:])
                    if not got:
                        break
                    n += got
                data = n
            else:
                data = resp.read()
            t1 = time.monotonic()
        except (OSError, http.client.HTTPException):
            self.close()
            raise
        if resp.will_close:
            self.close()
        return (resp.status, {k.lower(): v for k, v in resp.getheaders()},
                data, t0, t1)


class Worker(threading.Thread):
    """One closed-loop client."""

    def __init__(self, gen: "Generator", client: int, endpoint: str):
        super().__init__(daemon=True, name=f"bench-client-{client}")
        self.gen, self.client = gen, client
        self.conn = S3Conn(endpoint, gen.spec["access_key"],
                           gen.spec["secret_key"])
        self.stream = OpStream(gen.mix, gen.spec["seed"], client)
        self.records: list = []
        self.errors: list[str] = []
        self.mismatches: list[str] = []
        self.buf = bytearray(max(gen.mix["sizes"]) + 1)
        self.todo: list = []
        self.go = threading.Event()
        self.idle = threading.Event()
        self.idle.set()

    def one(self, op: str, key: str, size: int, bidx: int) -> None:
        body, md5, sha = self.gen.bodies.get(size, bidx)
        path = f"/{self.gen.spec['bucket']}/{key}"
        t0 = time.monotonic()
        try:
            if op == "PUT":
                st, h, data, t0, t1 = self.conn.request("PUT", path, body,
                                                        sha)
                ok = st == 200
                if ok and h.get("etag", "").strip('"') != md5:
                    ok = False
                    self.mismatches.append(
                        f"PUT {key}: ETag {h.get('etag')} != md5 {md5}")
                if ok:
                    self.stream.add(key, size, bidx)
            elif op == "GET":
                st, h, n, t0, t1 = self.conn.request("GET", path,
                                                     into=self.buf)
                ok, data = st == 200, n
                if ok and not (n == size and h.get("etag", "").strip('"')
                               == md5 and memoryview(self.buf)[:n] == body):
                    ok = False
                    self.conn.close()       # unread bytes may remain
                    self.mismatches.append(
                        f"GET {key}: {n} bytes, ETag {h.get('etag')}; the "
                        f"last acknowledged PUT had {size} bytes, md5 {md5}")
            elif op == "STAT":
                st, h, data, t0, t1 = self.conn.request("HEAD", path)
                ok = st == 200
                if ok and not (int(h.get("content-length", -1)) == size
                               and h.get("etag", "").strip('"') == md5):
                    ok = False
                    self.mismatches.append(
                        f"HEAD {key}: length {h.get('content-length')} ETag "
                        f"{h.get('etag')}; expected {size}, {md5}")
            else:
                st, h, data, t0, t1 = self.conn.request("DELETE", path)
                ok = st in (200, 204)
                self.stream.remove(key)     # acknowledged or not: unknown
            if not ok and st not in (200, 204):
                # the S3 error document names the cause (SlowDown, ...)
                said = bytes(data[:300]).decode("latin-1") \
                    if isinstance(data, (bytes, bytearray)) else ""
                self.errors.append(f"{op} {key}: HTTP {st} {said!r} "
                                   f"Retry-After={h.get('retry-after')}")
        except (OSError, http.client.HTTPException) as e:
            t1, ok = time.monotonic(), False
            self.errors.append(f"{op} {key}: {type(e).__name__}: {e}")
            if op == "DELETE":
                self.stream.remove(key)
        self.records.append([self.client, op, t0, t1, size, ok])

    def run(self) -> None:
        while True:
            self.go.wait()
            if self.gen.quit:
                break
            if self.todo:                       # preload
                for key, size, bidx in self.todo:
                    self.one("PUT", key, size, bidx)
                self.todo = []
                self.go.clear()
                self.idle.set()
                continue
            while not self.gen.stop:
                self.one(*self.stream.next())
            self.go.clear()
            self.idle.set()
        self.conn.close()


class Generator:
    def __init__(self, spec: dict):
        self.spec, self.mix = spec, spec["mix"]
        self.stop = self.quit = False
        self.bodies = BodyPool(spec["seed"], self.mix["sizes"],
                               self.mix["bodies_per_size"])
        self.workers = [Worker(self, c, ep)
                        for c, ep in spec["clients"]]
        for w in self.workers:
            w.start()

    def _release(self) -> None:
        for w in self.workers:
            w.idle.clear()
            w.go.set()

    def _wait_idle(self) -> None:
        for w in self.workers:
            w.idle.wait()

    def connect(self) -> dict:
        """Every parked worker's connection, opened or proven still open,
        one after the other."""
        path = f"/{self.spec['bucket']}"
        return {"connections": len(self.workers),
                "repeated": sum(w.conn.open(path) for w in self.workers)}

    def preload(self, plan: list) -> dict:
        mine = {w.client: w for w in self.workers}
        for c, key, size, bidx in plan:
            if c in mine:
                mine[c].todo.append((key, size, bidx))
        busy = [w for w in self.workers if w.todo]
        for w in busy:
            w.idle.clear()
            w.go.set()
        for w in busy:
            w.idle.wait()
        errors = [e for w in self.workers for e in w.errors]
        return {"preloaded": sum(len(w.records) for w in self.workers),
                "errors": len(errors), "error_samples": errors[:3]}

    def start(self) -> dict:
        self.stop = False
        self._release()
        return {"started": len(self.workers)}

    def finish(self) -> dict:
        """Stop after the operation in flight; hand everything back."""
        self.stop = True
        self._wait_idle()
        self.quit = True
        for w in self.workers:
            w.go.set()
        for w in self.workers:
            w.join(30)
        return {
            "records": [r for w in self.workers for r in w.records],
            "errors": [e for w in self.workers for e in w.errors],
            "mismatches": [e for w in self.workers for e in w.mismatches],
            "live": {k: v for w in self.workers
                     for k, v in w.stream.live.items()},
            "delete_as_put": sum(w.stream.delete_as_put
                                 for w in self.workers)}


def main() -> int:
    gen = Generator(json.loads(sys.stdin.readline()))
    print(json.dumps({"ready": len(gen.workers)}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "connect":
            out = gen.connect()
        elif cmd["cmd"] == "preload":
            out = gen.preload(cmd["plan"])
        elif cmd["cmd"] == "start":
            out = gen.start()
        elif cmd["cmd"] == "stop":
            print(json.dumps(gen.finish()), flush=True)
            return 0
        else:
            out = {"error": f"unknown command {cmd['cmd']!r}"}
        print(json.dumps(out), flush=True)
    gen.finish()        # the harness went away: stop with it
    return 1


if __name__ == "__main__":
    sys.exit(main())
