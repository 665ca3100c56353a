"""Per-rule canaries for the AST lint framework (minio_tpu/analysis/).

Every shipped rule must provably catch a seeded violation — a tiny bad
module string it MUST flag — and pass its clean twin, or the tier-1
lint gate is not evidence.  The CLI contract rides along: ``python -m
minio_tpu.analysis --json`` exits non-zero with a machine-readable
report on a seeded violation and exits 0 over the real tree.
"""

import json
import subprocess
import sys
import textwrap

from minio_tpu.analysis import run_tree
from minio_tpu.analysis.core import default_repo_root


_case = [0]


def _lint(tmp_path, files, docs=None):
    """Write ``files`` under a FRESH <case>/minio_tpu root (the scoped
    rules key off that prefix; isolation keeps one call's fixtures out
    of the next call's findings) and run every rule over them."""
    _case[0] += 1
    root = tmp_path / f"case{_case[0]}"
    for rel, src in files.items():
        p = root / "minio_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    for rel, text in (docs or {}).items():
        p = root / "docs" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return run_tree(repo=str(root))


def _rules_hit(findings):
    return {f.rule for f in findings}


# -- absorbed rules ----------------------------------------------------------

def test_parse_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": "def broken(:\n"})
    assert _rules_hit(bad) == {"parse"}
    assert "does not parse" in bad[0].message


def test_bare_except_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": """
        try:
            x = 1
        except:
            pass
        """})
    assert any(f.rule == "bare-except" and f.line == 4 for f in bad), bad
    clean = _lint(tmp_path, {"m.py": """
        try:
            x = 1
        except ValueError:
            x = 2
        """})
    assert not clean, clean


def test_mutable_default_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": "def f(a, b=[]):\n    return b\n"})
    assert any(f.rule == "mutable-default" and "f" in f.message
               for f in bad), bad
    clean = _lint(tmp_path,
                  {"m.py": "def f(a, b=None):\n    return b\n"})
    assert not clean, clean


def test_unused_import_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": "import os\nimport sys\nprint(sys)\n"})
    assert any(f.rule == "unused-import" and "os" in f.message
               for f in bad), bad
    # the historical noqa marker still exempts side-effect imports —
    # but only WITH a reason (the suppression-grammar contract)
    clean = _lint(tmp_path, {
        "m.py": "import os  # noqa — registry side effect\n"})
    assert not clean, clean
    bare = _lint(tmp_path, {"m.py": "import os  # noqa: F401\n"})
    assert any("needs a reason" in f.message for f in bare), bare


def test_whole_body_read_canary(tmp_path):
    bad = _lint(tmp_path, {"s3/h.py": """
        def handler(layer, self):
            data = layer.get_object("b", "k")
            body = self.rfile.read()
            return data, body
        """})
    msgs = [f.message for f in bad if f.rule == "whole-body-read"]
    assert any("get_object" in m for m in msgs), bad
    assert any("read()" in m for m in msgs), bad
    # the s3select materialization shape + its documented-fallback marker
    bad2 = _lint(tmp_path, {"s3select/m.py": """
        def materialize(src):
            return b"".join(src)
        """})
    assert any("join() materializes" in f.message for f in bad2), bad2
    clean = _lint(tmp_path, {"s3select/m.py": """
        def materialize(src):
            return b"".join(src)   # whole-body-ok — documented fallback
        """})
    assert not clean, clean
    # a reason-less legacy marker does not silently suppress
    bare = _lint(tmp_path, {"s3select/m.py": """
        def materialize(src):
            return b"".join(src)   # whole-body-ok
        """})
    assert any("without a reason" in f.message for f in bare), bare
    # ranged reads and the exempt client module stay unflagged
    clean2 = _lint(tmp_path, {"s3/h.py": """
        def handler(layer):
            return layer.get_object("b", "k", 0, 1024)
        """})
    assert not clean2, clean2


# -- concurrency rules -------------------------------------------------------

def test_lock_discipline_bare_acquire_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": """
        def f(self):
            self._mu.acquire()
            self.n += 1
            self._mu.release()
        """})
    assert any(f.rule == "lock-discipline" and "bare" in f.message
               for f in bad), bad
    clean = _lint(tmp_path, {"m.py": """
        def f(self):
            self._mu.acquire()
            try:
                self.n += 1
            finally:
                self._mu.release()
        """})
    assert not clean, clean


def test_lock_discipline_blocking_call_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": """
        import time

        def f(self, sock, th, fut):
            with self._mu:
                time.sleep(1.0)
                sock.sendall(b"x")
                th.join()
                fut.result()
        """})
    msgs = [f.message for f in bad if f.rule == "lock-discipline"]
    assert len(msgs) == 4, bad
    assert all("inside a `with self._mu` body" in m for m in msgs)
    # cond.wait on the held condition RELEASES it: not blocking;
    # nested function bodies do not run under the lock
    clean = _lint(tmp_path, {"m.py": """
        import time

        def f(self, items):
            with self._cv:
                self._cv.wait(0.1)
                later = [x for x in items]

                def cb():
                    time.sleep(1.0)
                return cb
        """})
    assert not clean, clean


def test_thread_discipline_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": """
        import threading

        def f(work):
            threading.Thread(target=work).start()
            threading.Thread(target=work, daemon=True).start()
            threading.Thread(target=work, daemon=True,
                             name="worker-1").start()
        """})
    msgs = [f.message for f in bad if f.rule == "thread-discipline"]
    # site 1: no daemon AND no name; site 2: no name; site 3: bad prefix
    assert len(msgs) == 4, bad
    assert sum("daemon" in m for m in msgs) == 1
    assert sum("anonymous" in m for m in msgs) == 2
    assert sum("must start" in m for m in msgs) == 1
    clean = _lint(tmp_path, {"m.py": """
        import threading

        def f(work, i):
            threading.Thread(target=work, daemon=True,
                             name=f"mt-canary-{i}").start()
        """})
    assert not clean, clean


def test_swallowed_exception_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": """
        def f():
            try:
                risky()
            except Exception:
                pass
        """})
    assert any(f.rule == "swallowed-exception" for f in bad), bad
    # narrow catches, handled bodies, and reasoned swallows all pass
    clean = _lint(tmp_path, {"m.py": """
        def f(log):
            try:
                risky()
            except OSError:
                pass
            try:
                risky()
            except Exception as e:  # noqa: BLE001 — surfaced to caller
                pass
            try:
                risky()
            except Exception:  # mt-lint: ok(swallowed-exception) probe only
                pass
            try:
                risky()
            except Exception:
                log.error("boom")
        """})
    assert not clean, clean


def test_kvconfig_drift_canary(tmp_path):
    files = {"utils/kvconfig.py": """
        def register_subsys(name, defaults):
            pass

        register_subsys("canary", {"knob_a": "1", "knob_b": "2"})
        register_subsys("wired", {"w": "1"})
        """,
             "srv.py": """
        def reload_wired_config(cfg):
            return cfg.get("wired", "w")
        """}
    bad = _lint(tmp_path, files,
                docs={"config.md": "| `wired.w` | live |"})
    msgs = [f.message for f in bad if f.rule == "kvconfig-drift"]
    assert any("canary.knob_a" in m and "not documented" in m
               for m in msgs), bad
    assert any("canary.knob_b" in m for m in msgs)
    assert any("'canary' is not read from any" in m for m in msgs)
    assert not any("wired" in m for m in msgs), msgs
    clean = _lint(tmp_path, {
        "utils/kvconfig.py": """
        def register_subsys(name, defaults):
            pass

        register_subsys(  # mt-lint: ok(kvconfig-drift) canary fixture
            "canary", {"knob_a": "1", "knob_b": "2"})
        register_subsys("wired", {"w": "1"})
        """,
        "srv.py": files["srv.py"]},
        docs={"config.md": "| `wired.w` | `canary.knob_a` "
                           "| `canary.knob_b` |"})
    assert not clean, clean


def test_obs_docs_drift_canary(tmp_path):
    src = {"m.py": """
        from ..obs import stages as _stages

        def serve():
            with _stages.stage("bogus_stage_x"):
                pass
            _stages.add_async("rpc_leg_y", 1)

        def scrape(mtr):
            mtr.inc("mt_forensic_bogus_total")
        """}
    bad = _lint(tmp_path, src,
                docs={"observability.md": "# obs\nnothing here\n"})
    msgs = [f.message for f in bad if f.rule == "obs-docs-drift"]
    assert any("bogus_stage_x" in m for m in msgs), bad
    assert any("rpc_leg_y" in m for m in msgs), msgs
    assert any("mt_forensic_bogus_total" in m for m in msgs), msgs
    clean = _lint(tmp_path, src, docs={"observability.md":
                                       "| `bogus_stage_x` | doc |\n"
                                       "| `rpc_leg_y` | doc |\n"
                                       "`mt_forensic_bogus_total`\n"})
    assert "obs-docs-drift" not in _rules_hit(clean), clean


def test_obs_docs_drift_watchdog_canary(tmp_path):
    """The watchdog extension of the drift rule: RULE_NAMES catalog
    entries and mt_alert_*/mt_history_* family literals (including the
    ``# TYPE`` declaration form scrapes emit through f-strings) must
    be documented like stage names."""
    src = {"obs/w.py": '''
        RULE_NAMES = (
            "bogus_rule_x",
            "bogus_rule_y",
        )

        def scrape(n):
            lines = ["# TYPE mt_alert_bogus_total counter"]
            lines.append(f"mt_history_bogus_series {n}")
            return lines
        '''}
    bad = _lint(tmp_path, src,
                docs={"observability.md": "# obs\nnothing here\n"})
    msgs = [f.message for f in bad if f.rule == "obs-docs-drift"]
    assert any("watchdog rule" in m and "bogus_rule_x" in m
               for m in msgs), bad
    assert any("bogus_rule_y" in m for m in msgs), msgs
    assert any("mt_alert_bogus_total" in m for m in msgs), msgs
    assert any("mt_history_bogus_series" in m for m in msgs), msgs
    clean = _lint(tmp_path, src, docs={"observability.md":
                                       "| `bogus_rule_x` | doc |\n"
                                       "| `bogus_rule_y` | doc |\n"
                                       "`mt_alert_bogus_total`\n"
                                       "`mt_history_bogus_series`\n"})
    assert "obs-docs-drift" not in _rules_hit(clean), clean


def test_tls_discipline_canary(tmp_path):
    bad = _lint(tmp_path, {"m.py": """
        import ssl

        def insecure(url, conn):
            ctx = ssl._create_unverified_context()
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            return ctx
        """})
    msgs = [f.message for f in bad if f.rule == "tls-discipline"]
    assert any("_create_unverified_context" in m for m in msgs), bad
    assert any("check_hostname" in m for m in msgs), bad
    assert any("CERT_NONE" in m for m in msgs), bad
    assert len(msgs) == 3, msgs
    # the pinned-context idiom (what secure/certs.py builds) is clean,
    # and check_hostname = True never trips the assignment check
    clean = _lint(tmp_path, {"m.py": """
        import ssl

        def pinned(ca):
            ctx = ssl.create_default_context(cafile=ca)
            ctx.check_hostname = True
            ctx.verify_mode = ssl.CERT_REQUIRED
            return ctx
        """})
    assert not clean, clean
    # the suppression grammar is honored (reason mandatory)
    supp = _lint(tmp_path, {"m.py": """
        import ssl

        def probe():
            return ssl.CERT_NONE  # mt-lint: ok(tls-discipline) scanner fixture needs the constant
        """})
    assert not supp, supp


def test_named_skip_canary(tmp_path):
    """Skips without a named reason in tests/ are findings; a
    positional message, a reason= kwarg, or a runtime expression
    (e.g. ``md5_device.unavailable_reason()``) all count as named."""
    from minio_tpu.analysis import run_tree as _run
    root = tmp_path / "nsk"
    (root / "minio_tpu").mkdir(parents=True)
    t = root / "tests"
    t.mkdir()
    (t / "test_bad.py").write_text(textwrap.dedent("""
        import pytest

        @pytest.mark.skipif(True)
        def test_a():
            pytest.skip()

        def test_b():
            pytest.skip("")

        @pytest.mark.skip
        def test_c():
            pass

        @pytest.mark.skip()
        def test_d():
            pass
        """))
    (t / "test_clean.py").write_text(textwrap.dedent("""
        import pytest
        from somewhere import unavailable_reason

        @pytest.mark.skipif(True, reason="no device on this host")
        def test_a():
            pytest.skip(unavailable_reason())

        def test_b():
            pytest.skip("no native engine")

        def test_c():
            pytest.skip()  # mt-lint: ok(named-skip) canary fixture

        @pytest.mark.skip(reason="tier needs hardware")
        def test_d():
            pass
        """))
    ns = [f for f in _run(repo=str(root)) if f.rule == "named-skip"]
    assert len(ns) == 5, ns
    assert all(f.path == "tests/test_bad.py" for f in ns), ns


def test_suppression_grammar_is_itself_linted(tmp_path):
    # reason-less suppression: the target finding is silenced but the
    # marker itself fails the run
    bad = _lint(tmp_path, {"m.py": """
        def f():
            try:
                risky()
            except Exception:  # mt-lint: ok(swallowed-exception)
                pass
        """})
    assert _rules_hit(bad) == {"suppression"}, bad
    assert "without a reason" in bad[0].message
    # unknown rule id in a marker is a finding too
    bad2 = _lint(tmp_path, {"m.py": """
        x = 1  # mt-lint: ok(made-up-rule) because reasons
        """})
    assert any("unknown rule" in f.message for f in bad2), bad2


# -- the CLI contract --------------------------------------------------------

def test_cli_json_exits_nonzero_with_report(tmp_path):
    pkg = tmp_path / "minio_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text("try:\n    x = 1\nexcept:\n    pass\n")
    r = subprocess.run(
        [sys.executable, "-m", "minio_tpu.analysis", "--json",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=default_repo_root())
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["count"] == 1
    f = doc["findings"][0]
    assert f["rule"] == "bare-except" and f["line"] == 3
    assert f["path"] == "minio_tpu/m.py"


def test_cli_clean_over_real_tree():
    """The CI gate: the shipped tree lints clean through the exact
    entry point a pipeline would call."""
    r = subprocess.run(
        [sys.executable, "-m", "minio_tpu.analysis"],
        capture_output=True, text=True, cwd=default_repo_root())
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


def test_rule_subset_flag(tmp_path):
    pkg = tmp_path / "minio_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "import os\ntry:\n    x = 1\nexcept:\n    pass\n")
    r = subprocess.run(
        [sys.executable, "-m", "minio_tpu.analysis", "--json",
         "--root", str(tmp_path), "--rule", "unused-import"],
        capture_output=True, text=True, cwd=default_repo_root())
    doc = json.loads(r.stdout)
    assert [f["rule"] for f in doc["findings"]] == ["unused-import"]


def test_pool_routing_canary(tmp_path):
    bad = _lint(tmp_path, {"s3/h.py": """
        def shape(layer):
            return layer.pools[0].set_drive_count
        """})
    assert any(f.rule == "pool-routing" and "pools[0]" in f.message
               for f in bad), bad
    # negative literals hardwire a position just the same
    bad2 = _lint(tmp_path, {"s3/h.py": """
        def last(layer):
            return layer.pools[-1]
        """})
    assert any(f.rule == "pool-routing" for f in bad2), bad2
    # a computed index came FROM the router — clean
    clean = _lint(tmp_path, {"s3/h.py": """
        def route(layer, bucket, name):
            i = layer.get_pool_idx(bucket, name)
            return layer.pools[i]
        """})
    assert not clean, clean
    # the pools layer itself owns placement — exempt
    clean2 = _lint(tmp_path, {"objectlayer/pools.py": """
        def sysvol(self):
            return self.pools[0]
        """})
    assert not clean2, clean2
    # reasoned suppression honored (the server.py shape probe idiom)
    clean3 = _lint(tmp_path, {"s3/h.py": """
        def shape(layer):
            return layer.pools[0]  # mt-lint: ok(pool-routing) shape probe
        """})
    assert not clean3, clean3


def test_span_discipline_canary(tmp_path):
    # captures the request id into a pool fan-out without the parent
    bad = _lint(tmp_path, {"objectlayer/fan.py": """
        from ..obs import trace as _trace

        def fanout(self, fn, items):
            rid = _trace.get_request_id()

            def run(item):
                _trace.set_request_id(rid)
                return fn(item)
            return self._pool.map(run, items)
        """})
    assert any(f.rule == "span-discipline" and "fanout" in f.message
               for f in bad), bad
    # Thread spawn counts as a submission just the same
    bad2 = _lint(tmp_path, {"parallel/fan.py": """
        import threading
        from ..obs import trace as _trace

        def spawn(fn):
            rid = _trace.get_request_id()

            def run():
                _trace.set_request_id(rid)
                fn()
            threading.Thread(target=run, daemon=True,
                             name="mt-fan").start()
        """})
    assert any(f.rule == "span-discipline" for f in bad2), bad2
    # the _with_request_id shape: parent rides beside the rid — clean
    clean = _lint(tmp_path, {"objectlayer/fan.py": """
        from ..obs import trace as _trace

        def fanout(self, fn, items):
            rid = _trace.get_request_id()
            parent = _trace.get_span_parent()

            def run(item):
                _trace.set_request_id(rid)
                _trace.set_span_parent(parent)
                return fn(item)
            return self._pool.map(run, items)
        """})
    assert not clean, clean
    # no contextvar capture: plain parallelism stays unflagged
    clean2 = _lint(tmp_path, {"storage/fan.py": """
        def fanout(self, fn, items):
            return self._pool.map(fn, items)
        """})
    assert not clean2, clean2
    # outside the storage/parallel/objectlayer scope — unflagged
    clean3 = _lint(tmp_path, {"s3/fan.py": """
        from ..obs import trace as _trace

        def fanout(self, fn, items):
            rid = _trace.get_request_id()
            return self._pool.map(lambda i: (rid, fn(i)), items)
        """})
    assert not clean3, clean3


def test_layering_canary(tmp_path):
    # the object layer reaching around the codec facade, lazily too
    bad = _lint(tmp_path, {"objectlayer/put.py": """
        from ..ops.codec import Erasure
        from ..ops import gf8

        def encode(data):
            from ..ops import rs_mesh
            import minio_tpu.ops.gf8_native as native
            return Erasure, gf8, rs_mesh, native
        """})
    hits = sorted((f.line, f.message.split(" — ")[0])
                  for f in bad if f.rule == "layering")
    assert hits == [(6, "imports ops.rs_mesh"),
                    (7, "imports ops.gf8_native")], bad
    # the whole package is never in the allowed set
    bad2 = _lint(tmp_path, {"objectlayer/x.py": """
        from .. import ops

        def f():
            return ops
        """})
    assert any(f.rule == "layering" and "ops.*" in f.message
               for f in bad2), bad2
    # the host hashing library takes arithmetic only; the kernels never
    # call up into the object layer or the S3 front
    bad3 = _lint(tmp_path, {
        "hashing/bitrot.py": """
            from ..ops.gf8 import ceil_frac

            def device_leg():
                from ..ops import device
                return device, ceil_frac
            """,
        "ops/codec.py": """
            def up():
                from ..objectlayer import erasure_object
                from minio_tpu.s3.server import S3Server
                return erasure_object, S3Server
            """})
    assert sorted((f.path, f.line) for f in bad3
                  if f.rule == "layering") == [
        ("minio_tpu/hashing/bitrot.py", 5),
        ("minio_tpu/ops/codec.py", 3),
        ("minio_tpu/ops/codec.py", 4)], bad3
    # the seam respected — and files outside the rule's table (a device
    # module that lives in hashing/, the parallel plane) are not its
    # business
    clean = _lint(tmp_path, {
        "objectlayer/put.py": """
            from ..hashing import bitrot
            from ..ops import gf8
            from ..ops.codec import Erasure

            def f():
                return bitrot, gf8, Erasure
            """,
        "hashing/md5_device.py": """
            from ..ops import device

            def f():
                return device
            """,
        "ops/codec.py": """
            from ..hashing import bitrot
            from . import gf8

            def f():
                from ..parallel import batcher
                return bitrot, gf8, batcher
            """})
    assert not clean, clean


def test_label_cardinality_canary(tmp_path):
    # shape A: a counter-registry call labelling an mt_ family by a
    # request-derived key outside the bounded metering registry
    bad = _lint(tmp_path, {"s3/m.py": """
        def record(metrics, bucket, tenant):
            metrics.inc("mt_requests_total",
                        {"bucket": bucket, "api": "GetObject"})
            metrics.inc("mt_bytes_total", labels={"tenant": tenant})
        """})
    msgs = [f.message for f in bad if f.rule == "label-cardinality"]
    assert len(msgs) == 2, bad
    assert any("mt_requests_total" in m and "bucket" in m
               for m in msgs), msgs
    assert any("mt_bytes_total" in m and "tenant" in m
               for m in msgs), msgs
    # shape B: a hand-rendered sample line carrying the label in the
    # constant head of an f-string
    bad2 = _lint(tmp_path, {"obs/m.py": """
        def render(key, n):
            return f'mt_hot_total{{key="{key}"}} {n}'
        """})
    assert any(f.rule == "label-cardinality" and "hand-rendered" in
               f.message for f in bad2), bad2
    # bounded labels (api/node/pool) are fine anywhere, and the
    # metering registry itself is exempt — it IS the bound
    clean = _lint(tmp_path, {
        "s3/m.py": """
            def record(metrics):
                metrics.inc("mt_requests_total", {"api": "GetObject"})
            """,
        "obs/metering.py": """
            def render(bucket, n):
                return f'mt_bucket_requests_total{{bucket="{bucket}"}} {n}'
            """,
    }, docs={"observability.md":
             "`mt_requests_total` `mt_bucket_requests_total`"})
    assert not clean, clean
