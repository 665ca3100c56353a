"""The one general traffic generator.  A mix is a data file under
``benchmarks/traffic/``; this module turns (mix, seed, client index) into
that client's operation stream.  Nothing here knows a cell's name.

Mix file keys:
  clients          closed-loop client threads (warp --concurrent)
  client_procs     processes they are spread over
  ops              {"GET": w, "STAT": w, "PUT": w, "DELETE": w} weights
  sizes            palette of object sizes in bytes, drawn with equal weight
  preload_objects  objects PUT during set-up, dealt round-robin to clients
  read_keys        {"dist": "uniform"} or {"dist": "zipf", "s": 0.99}: how
                   GET and STAT pick a key (zipf: rank r has weight r**-s).
                   "pool": "own" (default) ranks the client's own live
                   keys, oldest first.  "pool": "preloaded" makes the
                   preloaded objects ONE pool that every client reads in
                   the same rank order (rank = preload order, sizes drawn
                   independently of it): the popularity law holds over the
                   whole deployment, not per client.  Those objects are
                   immutable during the run; the client's own keys follow
                   them in its rank order
  own_preloaded    with "pool": "preloaded": this many of the preloaded
                   objects per client are that client's own (deletable)
                   keys, not part of the shared pool
  min_pool         a DELETE drawn while the client's own pool holds this
                   few keys or fewer is issued as a PUT instead (counted)
  bodies_per_size  distinct seeded bodies per size; every PUT sends one
  rehearse         overrides used by --rehearse only (tiny sizes)

Each client owns the keys it writes and deletes: no operation can fail by
racing another client, the op sequence of a client is a function of the
seed alone, and read-your-write is checked against that client's own last
acknowledged PUT.  A shared pool is only read.  The harness reads every
surviving key back from another connection after the window.
"""

from __future__ import annotations

import bisect
import hashlib
import random

import numpy as np

OPS = ("GET", "STAT", "PUT", "DELETE")


def effective(mix: dict, rehearse: bool) -> dict:
    return dict(mix, **mix.get("rehearse", {})) if rehearse else mix


def body_for(seed: int, size: int, idx: int) -> bytes:
    """The idx-th body of a size: the same bytes in every process."""
    return np.random.default_rng([seed, size, idx]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


class BodyPool:
    """Seeded bodies with their md5 and sha256, made once in set-up so
    that the window hashes nothing to send a PUT or to check a GET."""

    def __init__(self, seed: int, sizes: list[int], per_size: int):
        self.seed, self.per_size = seed, per_size
        self._cache: dict = {}
        for s in sizes:
            for i in range(per_size):
                self.get(s, i)

    def get(self, size: int, idx: int) -> tuple[bytes, str, str]:
        hit = self._cache.get((size, idx))
        if hit is None:
            b = body_for(self.seed, size, idx)
            hit = (b, hashlib.md5(b).hexdigest(),
                   hashlib.sha256(b).hexdigest())
            self._cache[(size, idx)] = hit
        return hit


def preload_plan(mix: dict, seed: int) -> list[tuple[int, str, int, int]]:
    """(client that PUTs it, key, size, body index) for every preloaded
    object, in preload order."""
    rng = random.Random(seed * 7919 + 1)
    out = []
    for j in range(mix["preload_objects"]):
        c = j % mix["clients"]
        out.append((c, f"c{c:02d}/pre{j:05d}", rng.choice(mix["sizes"]),
                    rng.randrange(mix["bodies_per_size"])))
    return out


def shared_pool(mix: dict, seed: int) -> list[tuple[str, int, int]]:
    """(key, size, body index) of the pool every client reads, in rank
    order; empty unless the mix says ``"pool": "preloaded"``.  The last
    ``own_preloaded * clients`` objects of the plan stay their clients'."""
    if mix.get("read_keys", {}).get("pool", "own") != "preloaded":
        return []
    plan = preload_plan(mix, seed)
    n = len(plan) - mix.get("own_preloaded", 0) * mix["clients"]
    return [(key, size, body) for _, key, size, body in plan[:max(n, 0)]]


class OpStream:
    """One client's operations, in order, from the seed."""

    def __init__(self, mix: dict, seed: int, client: int):
        self.mix, self.client = mix, client
        self.rng = random.Random(seed * 1_000_003 + client)
        self.names = [o for o in OPS if mix["ops"].get(o, 0) > 0]
        self.cum = []
        acc = 0
        for o in self.names:
            acc += mix["ops"][o]
            self.cum.append(acc)
        self.pool: list[str] = []            # own live keys, oldest first
        self.live: dict[str, tuple[int, int]] = {}   # key -> (size, body)
        self.shared: list[str] = []          # read by every client, fixed
        for key, size, body in shared_pool(mix, seed):
            self.shared.append(key)
            self.live[key] = (size, body)
        self.n_new = 0
        self.delete_as_put = 0
        self._zipf: list[float] = []         # cumulative rank weights

    def add(self, key: str, size: int, body: int) -> None:
        if key not in self.live:
            self.pool.append(key)
        self.live[key] = (size, body)

    def remove(self, key: str) -> None:
        if self.live.pop(key, None) is not None:
            self.pool.remove(key)

    def _read_key(self) -> str:
        ns = len(self.shared)
        n = ns + len(self.pool)
        rk = self.mix.get("read_keys", {"dist": "uniform"})
        if rk["dist"] == "uniform":
            i = self.rng.randrange(n)
        else:
            while len(self._zipf) < n:
                r = len(self._zipf) + 1
                self._zipf.append((self._zipf[-1] if self._zipf else 0.0)
                                  + 1.0 / r ** rk["s"])
            u = self.rng.random() * self._zipf[n - 1]
            i = bisect.bisect_left(self._zipf, u, 0, n - 1)
        return self.shared[i] if i < ns else self.pool[i - ns]

    def next(self) -> tuple[str, str, int, int]:
        """(op, key, size, body index).  The caller reports the outcome
        with add()/remove() once the server has acknowledged it."""
        x = self.rng.random() * self.cum[-1]
        op = self.names[bisect.bisect_right(self.cum, x)]
        if op != "PUT" and not (self.pool or self.shared):
            op = "PUT"                      # nothing to read yet
        if op == "DELETE" and len(self.pool) <= self.mix.get("min_pool", 1):
            op = "PUT"
            self.delete_as_put += 1
        if op == "PUT":
            self.n_new += 1
            return (op, f"c{self.client:02d}/n{self.n_new:06d}",
                    self.rng.choice(self.mix["sizes"]),
                    self.rng.randrange(self.mix["bodies_per_size"]))
        if op == "DELETE":
            key = self.pool[self.rng.randrange(len(self.pool))]
        else:
            key = self._read_key()
        return (op, key, *self.live[key])
