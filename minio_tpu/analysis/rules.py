"""The rule catalog (docs/static-analysis.md).

First four rules are the checks absorbed verbatim from
tests/test_static_analysis.py (same messages, same file:line); the
rest are tuned to this codebase's real concurrency failure classes —
the ones the writer planes, the MD5 lane scheduler, the codec batcher,
the egress senders, and the memory governor actually hit in PRs 5-9.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, Module, Rule

# -- helpers -----------------------------------------------------------------


def _last_segment(expr: ast.AST) -> str:
    """The trailing identifier of a dotted expression (``self._mu`` ->
    ``_mu``; ``SCHED`` -> ``SCHED``); empty for anything else."""
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return ""


def _marker_reason(line: str, marker_re: str) -> str | None:
    """Reason text following a legacy suppression marker on ``line``,
    or None when the marker is absent.  An empty string means the
    marker is there but reason-less — the caller flags it."""
    m = re.search(marker_re, line)
    if m is None:
        return None
    return m.group(1).strip("—-: ").strip()


_LOCK_SEG_RE = re.compile(
    r"(?:^|_)(lock|locks|mu|mutex|rlock|cond|cv|sem|semaphore)$",
    re.I)
_COND_SEG_RE = re.compile(
    r"(?:^|_)(cond|cv|not_empty|not_full|condition)$", re.I)


def _is_lockish(expr: ast.AST) -> bool:
    return bool(_LOCK_SEG_RE.search(_last_segment(expr)))


def _safe_unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:   # noqa: BLE001 — best-effort label for messages
        return "<expr>"


# -- the absorbed checks -----------------------------------------------------


class BareExceptRule(Rule):
    id = "bare-except"
    description = ("``except:`` without a type swallows "
                   "KeyboardInterrupt/SystemExit — name the exception")

    def check_module(self, mod: Module):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield Finding(mod.rel, node.lineno, self.id,
                              "bare except")


class MutableDefaultRule(Rule):
    id = "mutable-default"
    description = ("list/dict/set literals as parameter defaults are "
                   "shared across calls")

    def check_module(self, mod: Module):
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for d in list(node.args.defaults) + \
                        [d for d in node.args.kw_defaults if d]:
                    if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                        yield Finding(mod.rel, node.lineno, self.id,
                                      f"mutable default args: "
                                      f"{node.name}")


def _imported_names(node):
    """(bound name, lineno) entries."""
    if isinstance(node, ast.Import):
        for a in node.names:
            yield (a.asname or a.name.split(".")[0]), node.lineno
    elif isinstance(node, ast.ImportFrom):
        if node.module == "__future__":
            return                       # flag imports bind no name
        for a in node.names:
            if a.name == "*":
                continue
            yield (a.asname or a.name), node.lineno


class UnusedImportRule(Rule):
    id = "unused-import"
    description = ("imported name never referenced (side-effect "
                   "imports carry a trailing ``# noqa``)")

    def check_module(self, mod: Module):
        used = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Name):
                used.add(node.id)
        # names in __all__ strings and docstring references count
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                used.update(node.value.replace(",", " ").split())
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            for name, lineno in _imported_names(node):
                reason = _marker_reason(
                    mod.line_text(lineno),
                    r"#\s*noqa[:\s]*[A-Z0-9, ]*(.*)$")
                if reason:
                    continue             # side-effect/registry import
                if reason == "" and name not in used:
                    yield Finding(mod.rel, lineno, self.id,
                                  f"unused import {name}: its noqa "
                                  f"marker needs a reason")
                elif name not in used:
                    yield Finding(mod.rel, lineno, self.id,
                                  f"unused import: {name}")


# the test/replication S3Client's whole-object API is its contract;
# everything else in the request planes must read ranged or streamed
_WHOLE_BODY_EXEMPT = ("minio_tpu/s3/client.py",)
_WHOLE_BODY_SCOPE = ("minio_tpu/s3/", "minio_tpu/s3select/")


class WholeBodyReadRule(Rule):
    id = "whole-body-read"
    description = ("unbounded-memory pattern in the S3 request planes "
                   "(rangeless get_object / argless body read() / "
                   "whole-stream b''.join materialization)")

    def check_module(self, mod: Module):
        if mod.rel in _WHOLE_BODY_EXEMPT or \
                not mod.rel.startswith(_WHOLE_BODY_SCOPE):
            return
        in_select = mod.rel.startswith("minio_tpu/s3select/")
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or \
                    not isinstance(node.func, ast.Attribute):
                continue
            reason = _marker_reason(mod.line_text(node.lineno),
                                    r"#\s*whole-body-ok\s*(.*)$")
            if reason:
                continue
            if reason == "":
                yield Finding(mod.rel, node.lineno, self.id,
                              "whole-body-ok marker without a reason "
                              "— say why this materialization is a "
                              "documented fallback")
                continue
            attr = node.func.attr
            if attr == "get_object":
                kw = {k.arg for k in node.keywords}
                if len(node.args) < 3 and \
                        not ({"offset", "length"} & kw):
                    yield Finding(mod.rel, node.lineno, self.id,
                                  "whole-object get_object (no range)")
            elif attr == "read" and not node.args and not node.keywords:
                recv = _safe_unparse(node.func.value)
                if "rfile" in recv or "body" in recv or \
                        "reader" in recv:
                    yield Finding(mod.rel, node.lineno, self.id,
                                  "unbounded request-body read()")
            elif in_select and attr == "join" and \
                    isinstance(node.func.value, ast.Constant) and \
                    node.func.value.value == b"":
                # the PR-9 materializing-fallback shape: b"".join over
                # a chunk stream rebuilds the whole decoded object in
                # memory — every site must be a documented fallback
                # (bounded comprehensions over headers/fragments are
                # the normal join idiom and stay unflagged)
                if node.args and isinstance(
                        node.args[0],
                        (ast.Name, ast.Attribute, ast.Call)):
                    yield Finding(mod.rel, node.lineno, self.id,
                                  "whole-stream join() materializes "
                                  "the object")


# -- lock discipline ---------------------------------------------------------

# dotted-name suffixes that BLOCK: sockets/RPC wire ops, subprocesses,
# thread joins, sleeps, HTTP round-trips, future results, and device
# dispatches — none of which belong inside a ``with <lock>`` body on
# the threaded data plane (they stall every other waiter)
_BLOCKING_ATTRS = {
    "sendall", "recv", "recv_into", "accept", "connect",
    "getresponse", "urlopen", "check_output", "check_call",
    "communicate", "block_until_ready", "device_put",
}
_BLOCKING_QUALIFIED = {
    "time.sleep", "subprocess.run", "subprocess.call",
    "subprocess.check_call", "subprocess.check_output",
    "subprocess.Popen", "select.select", "socket.create_connection",
}
_THREADISH_RE = re.compile(
    r"(?:^|_)(thread|threads|worker|workers|sender|proc|t|th)\d*$",
    re.I)


class LockDisciplineRule(Rule):
    id = "lock-discipline"
    description = ("bare .acquire() without a finally-paired release, "
                   "or a blocking call (socket/RPC send, subprocess, "
                   "Thread.join, sleep, HTTP, Future.result, device "
                   "dispatch) inside a ``with <lock>`` body")

    def check_module(self, mod: Module):
        yield from self._bare_acquires(mod)
        yield from self._blocking_under_lock(mod)

    # bare .acquire(): an expression statement discarding the result,
    # with no enclosing try whose finally releases the same receiver
    def _bare_acquires(self, mod: Module):
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr == "acquire"
                    and _is_lockish(node.value.func.value)):
                continue
            recv = _safe_unparse(node.value.func.value)
            if self._finally_releases(mod, node, recv):
                continue
            yield Finding(
                mod.rel, node.lineno, self.id,
                f"bare {recv}.acquire() without a finally-paired "
                f"release — use `with {recv}:` or try/finally")

    @classmethod
    def _finally_releases(cls, mod: Module, node: ast.AST,
                          recv: str) -> bool:
        # idiom A: the acquire sits INSIDE a try whose finally releases
        for anc in mod.ancestors(node):
            if isinstance(anc, ast.Try) and \
                    cls._releases_in(anc.finalbody, recv):
                return True
        # idiom B: ``x.acquire()`` immediately followed by
        # ``try: ... finally: x.release()`` as the NEXT statement
        parent = mod.parent_of(node)
        for body in (getattr(parent, "body", None),
                     getattr(parent, "orelse", None),
                     getattr(parent, "finalbody", None)):
            if not body or node not in body:
                continue
            i = body.index(node)
            if i + 1 < len(body) and isinstance(body[i + 1], ast.Try) \
                    and cls._releases_in(body[i + 1].finalbody, recv):
                return True
        return False

    @staticmethod
    def _releases_in(stmts, recv: str) -> bool:
        for fin in stmts or ():
            for sub in ast.walk(fin):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr == "release" and \
                        _safe_unparse(sub.func.value) == recv:
                    return True
        return False

    def _blocking_under_lock(self, mod: Module):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.With):
                continue
            lock_items = [i.context_expr for i in node.items
                          if _is_lockish(i.context_expr)]
            if not lock_items:
                continue
            lock_texts = {_safe_unparse(i) for i in lock_items}
            for stmt in node.body:
                yield from self._scan_locked(mod, stmt, lock_texts)

    def _scan_locked(self, mod: Module, stmt: ast.AST,
                     lock_texts: set[str]):
        # lexical body only: nested function/class bodies run later,
        # not under this lock — prune them from the walk entirely
        out: list[Finding] = []

        def visit(n: ast.AST) -> None:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                return
            if isinstance(n, ast.Call):
                label = self._blocking_label(n, lock_texts)
                if label:
                    out.append(Finding(
                        mod.rel, n.lineno, self.id,
                        f"blocking call {label} inside a `with "
                        f"{'/'.join(sorted(lock_texts))}` body — move "
                        f"it out of the locked section"))
            for c in ast.iter_child_nodes(n):
                visit(c)

        visit(stmt)
        return out

    @staticmethod
    def _blocking_label(call: ast.Call,
                        lock_texts: set[str]) -> str | None:
        func = call.func
        dotted = _safe_unparse(func)
        if dotted in _BLOCKING_QUALIFIED or \
                any(dotted.endswith("." + q.split(".", 1)[1]) and
                    dotted.split(".")[-2:] == q.split(".")
                    for q in _BLOCKING_QUALIFIED):
            return dotted
        if not isinstance(func, ast.Attribute):
            return None
        attr = func.attr
        recv = func.value
        recv_txt = _safe_unparse(recv)
        if attr in _BLOCKING_ATTRS:
            return f"{recv_txt}.{attr}"
        if attr == "send" and not isinstance(recv, ast.Constant):
            seg = _last_segment(recv).lower()
            if any(s in seg for s in ("sock", "conn", "client",
                                      "chan", "pipe", "wire")):
                return f"{recv_txt}.send"
        if attr == "join":
            # Thread.join, never str.join: thread-ish receiver only
            if _THREADISH_RE.search(_last_segment(recv)):
                return f"{recv_txt}.join"
        if attr == "result":
            seg = _last_segment(recv).lower()
            if "fut" in seg or "future" in seg:
                return f"{recv_txt}.result"
        if attr == "wait":
            # cond.wait() RELEASES the lock it rides — only flag
            # waiting on something that is NOT the held lock
            # (Event.wait under a mutex stalls every other waiter)
            if recv_txt in lock_texts or \
                    _COND_SEG_RE.search(_last_segment(recv)):
                return None
            return f"{recv_txt}.wait"
        return None


# -- thread discipline -------------------------------------------------------


class ThreadDisciplineRule(Rule):
    id = "thread-discipline"
    description = ("every threading.Thread must pass an explicit "
                   "daemon= and a name=\"mt-...\" so leak/soak "
                   "thread-hygiene accounting can attribute it")

    def check_module(self, mod: Module):
        thread_names = self._thread_ctor_names(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not self._is_thread_ctor(node.func, thread_names):
                continue
            kwargs = {k.arg for k in node.keywords}
            if None in kwargs:           # **kw: can't see inside
                continue
            if "target" not in kwargs and not node.args:
                continue                 # a Thread subclass super().__init__?
            if "daemon" not in kwargs:
                yield Finding(mod.rel, node.lineno, self.id,
                              "threading.Thread without an explicit "
                              "daemon= flag")
            name_kw = next((k for k in node.keywords
                            if k.arg == "name"), None)
            if name_kw is None:
                yield Finding(mod.rel, node.lineno, self.id,
                              "anonymous threading.Thread — pass "
                              "name=\"mt-<subsystem>-...\"")
            else:
                prefix = self._static_prefix(name_kw.value)
                if prefix is not None and not prefix.startswith("mt-"):
                    yield Finding(mod.rel, node.lineno, self.id,
                                  f"thread name {prefix!r}... must "
                                  f"start with \"mt-\"")

    @staticmethod
    def _thread_ctor_names(mod: Module) -> set[str]:
        """Local bindings of threading.Thread (``from threading
        import Thread [as X]``)."""
        names = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and \
                    node.module == "threading":
                for a in node.names:
                    if a.name == "Thread":
                        names.add(a.asname or a.name)
        return names

    @staticmethod
    def _is_thread_ctor(func: ast.AST, local_names: set[str]) -> bool:
        if isinstance(func, ast.Attribute) and func.attr == "Thread":
            seg = _last_segment(func.value)
            return seg == "threading" or seg.endswith("threading") or \
                seg.lstrip("_") == "threading"
        if isinstance(func, ast.Name):
            return func.id in local_names
        return False

    @staticmethod
    def _static_prefix(value: ast.AST) -> str | None:
        """Literal prefix of a name expression, when determinable."""
        if isinstance(value, ast.Constant) and \
                isinstance(value.value, str):
            return value.value
        if isinstance(value, ast.JoinedStr) and value.values and \
                isinstance(value.values[0], ast.Constant) and \
                isinstance(value.values[0].value, str):
            return value.values[0].value
        if isinstance(value, ast.BinOp) and \
                isinstance(value.op, ast.Add) and \
                isinstance(value.left, ast.Constant) and \
                isinstance(value.left.value, str):
            return value.left.value
        return None                      # dynamic: accepted


# -- swallowed exceptions ----------------------------------------------------


class SwallowedExceptionRule(Rule):
    id = "swallowed-exception"
    description = ("``except ...: pass`` with no log, counter, or "
                   "written reason hides real failures")

    def check_module(self, mod: Module):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                continue                 # bare-except owns that case
            if not self._broad(node.type):
                continue                 # a NARROW typed catch with
                # pass is the close-path/parse-fallback idiom; only
                # catch-alls hide unknown failures
            if not all(isinstance(s, ast.Pass) for s in node.body):
                continue                 # logs/counts/re-raises: fine
            if self._has_reason(mod, node):
                continue
            yield Finding(
                mod.rel, node.lineno, self.id,
                "swallowed exception (`except ...: pass` with no "
                "log/counter) — handle it, count it, or suppress "
                "with a reason")

    @staticmethod
    def _broad(t: ast.AST) -> bool:
        names = []
        if isinstance(t, ast.Tuple):
            names = [_last_segment(e) for e in t.elts]
        else:
            names = [_last_segment(t)]
        return any(n in ("Exception", "BaseException") for n in names)

    @staticmethod
    def _has_reason(mod: Module, node: ast.ExceptHandler) -> bool:
        """The repo's long-standing idiom — ``# noqa: BLE001 — why``
        on the except/pass line — stays honored when reason text
        follows; the mt-lint grammar is handled by the runner."""
        lines = {node.lineno}
        for s in node.body:
            lines.add(s.lineno)
        for ln in lines:
            text = mod.line_text(ln)
            m = re.search(r"#\s*noqa[:\s]*([A-Z0-9]*)\s*(.*)", text)
            if m and m.group(2).strip("—- ").strip():
                return True
        return False


# -- kvconfig drift ----------------------------------------------------------


class KvconfigDriftRule(Rule):
    id = "kvconfig-drift"
    description = ("every registered kvconfig knob must appear as "
                   "``subsys.key`` in a docs/ table and its subsystem "
                   "must be reachable from a reload/load config path "
                   "(construction-time subsystems carry a suppression "
                   "with the reason)")

    _RELOADISH_RE = re.compile(r"(?:^|_)reload|^load$|^_load")

    def check_tree(self, mods: list[Module], repo: str):
        import os
        kv = next((m for m in mods
                   if m.rel.endswith("utils/kvconfig.py")), None)
        if kv is None:
            return
        docs_text = ""
        docs_dir = os.path.join(repo, "docs")
        if os.path.isdir(docs_dir):
            for f in sorted(os.listdir(docs_dir)):
                if f.endswith(".md"):
                    with open(os.path.join(docs_dir, f),
                              encoding="utf-8") as fh:
                        docs_text += fh.read()
        reachable = self._reload_constants(mods)
        for lineno, subsys, keys in self._registrations(kv):
            for key in keys:
                token = f"{subsys}.{key}"
                if token not in docs_text:
                    yield Finding(
                        kv.rel, lineno, self.id,
                        f"knob {token} is not documented in any "
                        f"docs/*.md table (docs/config.md)")
            if not self._reachable(subsys, reachable):
                yield Finding(
                    kv.rel, lineno, self.id,
                    f"subsystem '{subsys}' is not read from any "
                    f"reload_*_config/load path — admin SetConfigKV "
                    f"changes would never land; wire a reload or "
                    f"suppress with the construction-time reason")

    @staticmethod
    def _registrations(kv: Module):
        """(lineno, subsys, [keys]) per ``register_subsys`` call with
        a literal name + defaults dict."""
        for node in ast.walk(kv.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "register_subsys"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)):
                continue
            subsys = node.args[0].value
            keys = []
            if len(node.args) > 1 and isinstance(node.args[1],
                                                 ast.Dict):
                for k in node.args[1].keys:
                    if isinstance(k, ast.Constant) and \
                            isinstance(k.value, str):
                        keys.append(k.value)
            yield node.lineno, subsys, keys

    @classmethod
    def _reload_constants(cls, mods: list[Module]) -> set[str]:
        """String constants (incl. f-string fragments) inside every
        function whose name looks like a config (re)load path — plus
        one call hop (``_reload_egress_locked`` builds broker targets
        through ``target_from_config``, which owns the ``notify_*``
        subsystem strings)."""
        defs: dict[str, list] = {}
        for mod in mods:
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    defs.setdefault(node.name, []).append(node)
        roots = [n for name, nodes in defs.items()
                 if cls._RELOADISH_RE.search(name) for n in nodes]
        hop = set()
        for fn in roots:
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call):
                    callee = _last_segment(sub.func)
                    if callee in defs:
                        hop.add(callee)
        consts: set[str] = set()
        for fn in roots + [n for name in hop for n in defs[name]]:
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, str):
                    consts.add(sub.value)
        return consts

    @staticmethod
    def _reachable(subsys: str, consts: set[str]) -> bool:
        if subsys in consts:
            return True
        # f-string prefixes ("notify_" + kind) count as reaching the
        # whole family
        return any(c and c.endswith("_") and subsys.startswith(c)
                   for c in consts)


# -- obs docs drift ----------------------------------------------------------


class ObsDocsDriftRule(Rule):
    id = "obs-docs-drift"
    description = ("every X-ray stage name emitted in code "
                   "(``_stages.stage/add/add_async`` call sites + the "
                   "``STAGE_NAMES`` catalog), every watchdog rule "
                   "name (the ``RULE_NAMES`` catalog), and every "
                   "``mt_{s3_stage,forensic,flight,quorum,drive_op,"
                   "drive_call,read_leg,tpu_leg,"
                   "trace_tree,alert,history,bucket,tenant,metering,"
                   "commit}"
                   "_*`` metric family "
                   "literal must appear in docs/observability.md — an "
                   "operator reading the stage/rule/family catalog "
                   "must be able to trust it is complete")

    _FAMILY_RE = re.compile(
        r"^mt_(?:s3_stage|forensic|flight|quorum|drive_op|drive_call"
        r"|read_leg|tpu_leg|trace_tree"
        r"|alert|history|bucket|tenant|metering|commit)_\w+$")

    def check_tree(self, mods: list[Module], repo: str):
        import os
        doc_path = os.path.join(repo, "docs", "observability.md")
        try:
            with open(doc_path, encoding="utf-8") as fh:
                doc = fh.read()
        except OSError:
            doc = ""
        for mod in mods:
            for lineno, kind, token in self._tokens(mod):
                # anchored on the catalog's own rendering (a backticked
                # token): plain substring membership would be vacuously
                # satisfied by prose ('auth' inside 'authorization')
                if f"`{token}" not in doc:
                    yield Finding(
                        mod.rel, lineno, self.id,
                        f"{kind} {token!r} is emitted here but absent "
                        f"from docs/observability.md (stage/metrics "
                        f"catalog; list it as a backticked `{token}` "
                        f"entry)")

    @classmethod
    def _tokens(cls, mod: Module):
        """(lineno, kind, token) for stage names at ``_stages.stage/
        add/add_async`` call sites, entries of the ``STAGE_NAMES`` /
        ``RULE_NAMES`` catalogs, and matching metric family literals
        (bare strings, the constant head of an f-string sample line,
        and ``# TYPE`` declarations)."""
        catalogs = {"STAGE_NAMES": "stage name",
                    "RULE_NAMES": "watchdog rule"}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("stage", "add", "add_async") and \
                    _last_segment(node.func.value).lstrip("_") \
                    == "stages" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                yield node.lineno, "stage name", node.args[0].value
            elif isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id in catalogs
                    for t in node.targets) and \
                    isinstance(node.value, (ast.Tuple, ast.List)):
                kind = next(catalogs[t.id] for t in node.targets
                            if isinstance(t, ast.Name)
                            and t.id in catalogs)
                for el in node.value.elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, str):
                        yield el.lineno, kind, el.value
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    not mod.rel.startswith("minio_tpu/analysis/"):
                s = node.value
                if s.startswith("# TYPE "):
                    # the family a scrape declares IS emitted — the
                    # declaration line pins it even when the sample
                    # line's name lives in an f-string head
                    parts = s.split()
                    s = parts[2] if len(parts) >= 3 else ""
                else:
                    s = s.split(" ", 1)[0].split("{", 1)[0]
                if cls._FAMILY_RE.match(s):
                    yield node.lineno, "metric family", s


# -- label cardinality -------------------------------------------------------

# request-derived label keys: their value space is controlled by
# CLIENTS (bucket names, object keys, access keys), so a family
# carrying one has unbounded cardinality unless something bounds it
_REQUEST_LABELS = frozenset(
    {"bucket", "key", "object", "access_key", "tenant", "prefix"})
# the bounded emitters: the metering registry caps its tables at
# top-K sketch membership + an ``_other`` overflow row, and the
# renderer only echoes those bounded tables (incl. the crawler's
# per-bucket usage gauges — buckets are operator-created, not
# request-minted, and the bucket table itself is capped upstream)
_LABEL_CARDINALITY_EXEMPT = (
    "minio_tpu/obs/metering.py",
    "minio_tpu/admin/metrics.py",
)
_LABEL_IN_SAMPLE_RE = re.compile(
    r"[{,](?:" + "|".join(sorted(_REQUEST_LABELS)) + r')="')


class LabelCardinalityRule(Rule):
    id = "label-cardinality"
    description = ("an ``mt_*`` metric emission carrying a request-"
                   "derived label (bucket/key/object/access_key/"
                   "tenant/prefix) outside the bounded metering "
                   "registry grows one series per distinct client "
                   "value — unbounded scrape memory; route it through "
                   "obs/metering.py (top-K sketch gating + ``_other`` "
                   "overflow) instead")

    def check_module(self, mod: Module):
        if mod.rel in _LABEL_CARDINALITY_EXEMPT:
            return
        for node in ast.walk(mod.tree):
            # shape A: counter-registry calls —
            # ``_metrics.inc("mt_x_total", {"bucket": b})``
            if isinstance(node, ast.Call):
                fam = next(
                    (a.value for a in node.args
                     if isinstance(a, ast.Constant)
                     and isinstance(a.value, str)
                     and a.value.startswith("mt_")), None)
                if fam is None:
                    continue
                dicts = [a for a in node.args
                         if isinstance(a, ast.Dict)] + \
                        [k.value for k in node.keywords
                         if isinstance(k.value, ast.Dict)]
                for d in dicts:
                    hot = sorted(
                        k.value for k in d.keys
                        if isinstance(k, ast.Constant)
                        and k.value in _REQUEST_LABELS)
                    if hot:
                        yield Finding(
                            mod.rel, node.lineno, self.id,
                            f"family {fam} labelled by request-"
                            f"derived {'/'.join(hot)} — unbounded "
                            f"cardinality; go through the metering "
                            f"registry (obs/metering.py)")
            # shape B: hand-rendered sample lines —
            # ``f'mt_x_total{{bucket="{b}"}} 1'`` (the constant head
            # of an f-string carries both the family and the label)
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    node.value.startswith("mt_") and \
                    _LABEL_IN_SAMPLE_RE.search(node.value):
                yield Finding(
                    mod.rel, node.lineno, self.id,
                    f"hand-rendered sample line for "
                    f"{node.value.split('{', 1)[0]} carries a "
                    f"request-derived label — unbounded cardinality; "
                    f"go through the metering registry "
                    f"(obs/metering.py)")


# -- tls discipline ----------------------------------------------------------


class TlsDisciplineRule(Rule):
    id = "tls-discipline"
    description = ("TLS verification must never be weakened in the "
                   "production tree: ``ssl._create_unverified_context``, "
                   "``check_hostname = False`` assignments, and "
                   "``ssl.CERT_NONE`` are flagged (the runner walks "
                   "``minio_tpu`` only, so tests/ stays free to build "
                   "negative fixtures; the suppression grammar is "
                   "honored)")

    def check_module(self, mod: Module):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute):
                if node.attr == "_create_unverified_context":
                    yield Finding(
                        mod.rel, node.lineno, self.id,
                        "ssl._create_unverified_context disables "
                        "certificate verification — build a CA-pinned "
                        "context (secure/certs.py) instead")
                elif node.attr == "CERT_NONE":
                    yield Finding(
                        mod.rel, node.lineno, self.id,
                        "ssl.CERT_NONE disables peer verification — "
                        "pin the deployment CA instead")
            elif isinstance(node, ast.Assign):
                if not (isinstance(node.value, ast.Constant)
                        and node.value.value is False):
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) and \
                            tgt.attr == "check_hostname":
                        yield Finding(
                            mod.rel, node.lineno, self.id,
                            "check_hostname = False defeats hostname "
                            "verification — mint certs with the right "
                            "SANs (secure/pki.py does) instead")


# -- named skip --------------------------------------------------------------


class NamedSkipRule(Rule):
    id = "named-skip"
    description = ("every pytest.skip()/pytest.mark.skipif() in "
                   "tests/ must carry a non-empty reason — a path "
                   "that degrades (no device, no compiler, no .so) "
                   "must NAME why, or a silently-skipped tier reads "
                   "as coverage it does not have")

    def check_tree(self, mods: list[Module], repo: str):
        """tests/ is outside the runner's ``minio_tpu`` walk, so this
        rule parses it directly (the kvconfig-drift/docs discipline):
        the degradation contract lives in the tests."""
        import os
        tdir = os.path.join(repo, "tests")
        if not os.path.isdir(tdir):
            return
        for fname in sorted(os.listdir(tdir)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(tdir, fname)
            try:
                with open(path, encoding="utf-8") as fh:
                    src = fh.read()
                tree = ast.parse(src)
            except (OSError, SyntaxError):
                continue            # the parse rule owns broken files
            lines = src.splitlines()
            rel = f"tests/{fname}"
            for node in ast.walk(tree):
                # bare @pytest.mark.skip decorators (no call, so no
                # reason is even possible) are the purest silent skip
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    for dec in node.decorator_list:
                        if isinstance(dec, ast.Attribute) and \
                                _safe_unparse(dec).endswith(
                                    "mark.skip") and \
                                not self._suppressed(lines,
                                                     dec.lineno):
                            yield Finding(
                                rel, dec.lineno, self.id,
                                "@pytest.mark.skip without a reason "
                                "— name why this path degrades")
                    continue
                if not isinstance(node, ast.Call):
                    continue
                if self._suppressed(lines, node.lineno):
                    continue
                name = _safe_unparse(node.func)
                if name.endswith("pytest.skip") or name == "skip" \
                        or name.endswith("mark.skip"):
                    if not self._has_reason(node, positional=True):
                        yield Finding(
                            rel, node.lineno, self.id,
                            "pytest.skip() without a reason — name "
                            "why this path degrades")
                elif name.endswith(".skipif"):
                    if not self._has_reason(node, positional=False):
                        yield Finding(
                            rel, node.lineno, self.id,
                            "skipif without reason= — name why this "
                            "path degrades")

    @staticmethod
    def _suppressed(lines: list[str], lineno: int) -> bool:
        """tests/ sits outside the runner's suppression pass, so the
        grammar is honored here: a reasoned ``# mt-lint:
        ok(named-skip) why`` on the flagged line."""
        line = lines[lineno - 1] if lineno <= len(lines) else ""
        return bool(re.search(
            r"#\s*mt-lint:\s*ok\([^)]*named-skip[^)]*\)\s*\S", line))

    @staticmethod
    def _has_reason(node: ast.Call, positional: bool) -> bool:
        """True when a non-empty reason is present: a non-constant
        expression counts (it evaluates to the reason at runtime, e.g.
        ``md5_device.unavailable_reason()``); only a MISSING or
        empty-literal reason is a finding."""
        cands = []
        if positional and node.args:
            cands.append(node.args[0])
        for kw in node.keywords:
            if kw.arg == "reason":
                cands.append(kw.value)
        for c in cands:
            if isinstance(c, ast.Constant):
                if isinstance(c.value, str) and c.value.strip():
                    return True
            else:
                return True
        return False


class PoolRoutingRule(Rule):
    id = "pool-routing"
    description = ("``<x>.pools[<literal int>]`` outside "
                   "objectlayer/pools.py hardwires a pool position — "
                   "elastic topology (pool add/decommission) shifts "
                   "indexes, so route through the pools layer "
                   "(get_pool_idx/_find_pool) instead")

    _EXEMPT = "minio_tpu/objectlayer/pools.py"

    def check_module(self, mod: Module):
        if mod.rel == self._EXEMPT:
            # the pools layer OWNS placement: pool 0 is its documented
            # system-volume anchor, every other index flows through it
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Subscript):
                continue
            if _last_segment(node.value) != "pools":
                continue
            idx = node.slice
            if isinstance(idx, ast.UnaryOp) and \
                    isinstance(idx.op, ast.USub):
                idx = idx.operand
            if not (isinstance(idx, ast.Constant)
                    and isinstance(idx.value, int)):
                continue             # computed indexes came FROM the router
            yield Finding(
                mod.rel, node.lineno, self.id,
                f"direct pool indexing ({_safe_unparse(node)}) — "
                "pool positions shift on add/decommission; go through "
                "the pools layer's router instead")


# -- span discipline ---------------------------------------------------------

_POOLISH_RE = re.compile(
    r"(?:^|_)(pool|pools|executor|exec|tpe|workers)\d*$", re.I)
_SPAWN_METHODS = {"submit", "map", "apply_async"}


class SpanDisciplineRule(Rule):
    id = "span-discipline"
    description = ("a function in minio_tpu/{storage,parallel,"
                   "objectlayer} that captures the request contextvar "
                   "(get_request_id) AND hands work to another thread "
                   "(threading.Thread / pool .submit/.map/.apply_async) "
                   "must also propagate the span parent "
                   "(get_span_parent / push_span_parent — the "
                   "_with_request_id shape), or the child's spans "
                   "detach from the causal tree")

    _SCOPE = ("minio_tpu/storage/", "minio_tpu/parallel/",
              "minio_tpu/objectlayer/")

    def check_module(self, mod: Module):
        if not mod.rel.startswith(self._SCOPE):
            return
        thread_names = ThreadDisciplineRule._thread_ctor_names(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            has_rid = has_parent = False
            spawn_line = spawn_label = None
            # lexical scan incl. nested closures: the capture usually
            # lives in an inner runner while the submit is in the
            # outer fan-out — either way, one function owns both and
            # must carry the parent alongside the request id
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                name = sub.func.attr \
                    if isinstance(sub.func, ast.Attribute) \
                    else (sub.func.id
                          if isinstance(sub.func, ast.Name) else "")
                if name == "get_request_id":
                    has_rid = True
                elif name in ("get_span_parent", "push_span_parent"):
                    has_parent = True
                if spawn_line is None:
                    label = self._spawn_label(sub, thread_names)
                    if label:
                        spawn_line, spawn_label = sub.lineno, label
            if has_rid and spawn_line is not None and not has_parent:
                yield Finding(
                    mod.rel, spawn_line, self.id,
                    f"{node.name} captures get_request_id() and "
                    f"spawns work ({spawn_label}) without "
                    f"propagating the span parent — carry "
                    f"get_span_parent() into the child (the "
                    f"_with_request_id shape) or its spans detach "
                    f"from the causal tree")

    @staticmethod
    def _spawn_label(call: ast.Call,
                     thread_names: set[str]) -> str | None:
        if ThreadDisciplineRule._is_thread_ctor(call.func,
                                                thread_names):
            return "threading.Thread"
        if isinstance(call.func, ast.Attribute) and \
                call.func.attr in _SPAWN_METHODS:
            if call.func.attr == "apply_async" or \
                    _POOLISH_RE.search(
                        _last_segment(call.func.value)):
                return f"{_safe_unparse(call.func)}"
        return None


# -- layering ----------------------------------------------------------------


class LayeringRule(Rule):
    id = "layering"
    description = ("the codec seam, as an import rule: objectlayer/ "
                   "reaches ops/ only through ``codec`` (and ``gf8`` "
                   "for shard arithmetic), the host hashing library "
                   "takes only ``gf8`` from ops/, and ops/ imports "
                   "nothing from objectlayer/ or s3/ — function-level "
                   "imports count")

    # (importing file or directory, package under minio_tpu, the
    # submodules of it that may be imported) — exemptions are by file,
    # here, and nowhere else
    _ALLOWED = (
        ("minio_tpu/objectlayer/", "ops", frozenset({"codec", "gf8"})),
        ("minio_tpu/hashing/bitrot.py", "ops", frozenset({"gf8"})),
        ("minio_tpu/hashing/highwayhash.py", "ops", frozenset({"gf8"})),
        ("minio_tpu/ops/", "objectlayer", frozenset()),
        ("minio_tpu/ops/", "s3", frozenset()),
    )

    @staticmethod
    def _targets(mod: Module, node) -> list[str]:
        """Absolute dotted names one import statement reaches."""
        if isinstance(node, ast.Import):
            return [a.name for a in node.names]
        base: list[str] = []
        if node.level:
            pkg = mod.rel.split("/")[:-1]
            base = pkg[:len(pkg) - (node.level - 1)]
        if node.module:
            base = base + node.module.split(".")
        return [".".join(base + [a.name]) for a in node.names]

    def check_module(self, mod: Module):
        scoped = [(pkg, allowed) for where, pkg, allowed in self._ALLOWED
                  if mod.rel == where
                  or (where.endswith("/") and mod.rel.startswith(where))]
        if not scoped:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            for target in self._targets(mod, node):
                parts = target.split(".")
                if parts[0] != "minio_tpu" or len(parts) < 2:
                    continue
                for pkg, allowed in scoped:
                    if parts[1] != pkg:
                        continue
                    # ``from .. import ops`` / ``from ..ops import *``
                    # reach the whole package: never in the allowed set
                    sub = parts[2] if len(parts) > 2 else "*"
                    if sub not in allowed:
                        may = ", ".join(f"{pkg}.{a}"
                                        for a in sorted(allowed)) \
                            or "nothing"
                        yield Finding(
                            mod.rel, node.lineno, self.id,
                            f"imports {pkg}.{sub} — from {pkg}/ this "
                            f"file may import {may}")


ALL_RULES = [
    BareExceptRule,
    MutableDefaultRule,
    UnusedImportRule,
    WholeBodyReadRule,
    LockDisciplineRule,
    ThreadDisciplineRule,
    SwallowedExceptionRule,
    KvconfigDriftRule,
    ObsDocsDriftRule,
    LabelCardinalityRule,
    TlsDisciplineRule,
    NamedSkipRule,
    PoolRoutingRule,
    SpanDisciplineRule,
    LayeringRule,
]
