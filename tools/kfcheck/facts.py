"""kfcheck phase 1: per-file fact extraction (whole-program analysis).

The v1 checker ran each rule against one file at a time; the hazards
added in v2 are *cross-file* — a ``KFT_*`` env read is only wrong when
the typed registry (kungfu_tpu/utils/knobs.py) has no entry for it, a
metric name is only suspicious when the publisher spells it one way and
the doctor another, a chaos site is only dead when no plan in the whole
tree references it.  So the driver now runs two phases:

  1. THIS module walks every file once and extracts a small,
     JSON-serializable :data:`FileFacts` dict (env reads, KFT_*/metric
     string literals with their use context, chaos.point sites and plan
     references, a per-class lock/thread model).
  2. :mod:`tools.kfcheck.wprogram` joins the facts repo-wide and runs
     the four program passes over the joined model.

Facts are cached in ``tools/kfcheck/.cache.json`` keyed by (mtime,
size) plus a hash of this file, so `make lint` only re-parses files
that changed; ``--no-cache`` bypasses it.

Heuristic honesty: extraction is AST-shaped, not a points-to analysis.
Env-var names are resolved through same-file module-level string
constants only (``TOKEN_ENV = "KFT_CONTROL_TOKEN"``); a name imported
from another module is recorded unresolved and skipped by the passes.
"""
from __future__ import annotations

import ast
import hashlib
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .dataflow import collect_dataflow
from .engine import Module, Rule, iter_py_files
from .protocol import collect_protocol
from .rules import call_name, dotted, tail

# bump to invalidate every cached fact when the extraction shape changes
FACTS_SCHEMA = 3

DEFAULT_CACHE = Path(__file__).resolve().parent / ".cache.json"

# the analyzer must not analyze itself (its sources and tests are full
# of KFT_*/kungfu_tpu_* fixture literals that would poison the joined
# model with phantom knobs and one-off metric names)
PROGRAM_EXCLUDE = re.compile(
    r"(^|/)tools/kfcheck/|(^|/)tests/test_kfcheck\.py$")

KNOB_RE = re.compile(r"^KFT_[A-Z0-9_]+$")
METRIC_RE = re.compile(r"kungfu_tpu_[a-z0-9_]+")
SITE_RE = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")  # layer.operation[.phase]

_ENVIRON = {"os.environ", "environ", "_os.environ"}
_GETENV = {"os.getenv", "getenv", "_os.getenv"}

# attr names that ARE synchronization objects, not shared data
_LOCKISH = re.compile(r"lock|cond|mutex|guard", re.IGNORECASE)

# a `self.x = <one of these>()` marks x as a threading primitive /
# thread-safe container — exempt from the lock-discipline pass
_THREAD_PRIMS = {
    "Lock", "RLock", "Condition", "Event", "Semaphore", "BoundedSemaphore",
    "Barrier", "Thread", "Timer", "Queue", "SimpleQueue", "LifoQueue",
    "PriorityQueue", "deque",
}

# method calls that mutate their receiver in place
_MUTATORS = {
    "append", "appendleft", "extend", "add", "update", "insert", "remove",
    "discard", "pop", "popleft", "popitem", "clear", "setdefault", "put",
    "put_nowait", "sort", "reverse",
}


def lockish(name: str) -> bool:
    return bool(_LOCKISH.search(name)) or name.strip("_") == "cv"


def _self_attr(node: ast.AST) -> Optional[str]:
    """'x' when node is exactly ``self.x``, else None."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _is_const_value(node: ast.AST) -> bool:
    """True for values whose assignment is a GIL-atomic flag write
    (constants, +-constant) — excluded from the race model."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(node.operand,
                                                    ast.Constant):
        return True
    return False


class _AccessWalker:
    """Records every ``self.<attr>`` access in one method with its kind
    (read / flag-write / mutation) and whether a ``with self.<lock>:``
    is lexically held at that point."""

    def __init__(self, mod: Module, method: str, out: List[dict]):
        self.mod = mod
        self.method = method
        self.out = out
        self.handled: Set[int] = set()

    def _rec(self, node: ast.AST, attr: str, kind: str,
             locked: bool) -> None:
        line = getattr(node, "lineno", 1)
        self.out.append({
            "attr": attr, "method": self.method, "kind": kind,
            "locked": locked, "line": line,
            "symbol": self.mod.symbol_at(line),
            "snippet": self.mod.snippet_at(line),
        })

    def _lockish_ctx(self, expr: ast.AST) -> bool:
        attr = _self_attr(expr)
        if attr is not None and lockish(attr):
            self.handled.add(id(expr))
            return True
        return False

    def _mutation_target(self, node: ast.AST) -> Optional[str]:
        """attr name when node is a store through ``self.x`` —
        ``self.x[...]`` or ``self.x`` itself."""
        if isinstance(node, ast.Subscript):
            return _self_attr(node.value)
        return _self_attr(node)

    def walk(self, node: ast.AST, locked: bool) -> None:
        if isinstance(node, ast.With):
            if any(self._lockish_ctx(item.context_expr)
                   for item in node.items):
                locked = True
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            if isinstance(node, ast.AnnAssign) and node.value is None:
                targets = []  # bare annotation, not a write
            for tgt in targets:
                attr = self._mutation_target(tgt)
                if attr is None:
                    continue
                if isinstance(tgt, ast.Subscript):
                    self.handled.add(id(tgt.value))
                    kind = "mut"
                else:
                    self.handled.add(id(tgt))
                    kind = "mut"
                    if isinstance(node, (ast.Assign, ast.AnnAssign)) and \
                            node.value is not None and \
                            _is_const_value(node.value):
                        kind = "flag"
                if isinstance(node, ast.AugAssign):
                    kind = "mut"
                self._rec(tgt, attr, kind, locked)
        elif isinstance(node, ast.Delete):
            for tgt in node.targets:
                attr = self._mutation_target(tgt)
                if attr is not None:
                    self.handled.add(id(tgt))
                    if isinstance(tgt, ast.Subscript):
                        self.handled.add(id(tgt.value))
                    self._rec(tgt, attr, "mut", locked)
        elif isinstance(node, ast.Call):
            # self.x.append(...) — mutation of x; self._lock.acquire()
            # — lock op, not data access
            if isinstance(node.func, ast.Attribute):
                recv = _self_attr(node.func.value)
                if recv is not None:
                    if node.func.attr in _MUTATORS:
                        self.handled.add(id(node.func.value))
                        self._rec(node, recv, "mut", locked)
                    elif node.func.attr in ("acquire", "release",
                                            "locked", "notify",
                                            "notify_all", "wait"):
                        self.handled.add(id(node.func.value))
        elif isinstance(node, ast.Attribute) and id(node) not in self.handled:
            attr = _self_attr(node)
            if attr is not None and isinstance(node.ctx, ast.Load):
                self._rec(node, attr, "read", locked)
        for child in ast.iter_child_nodes(node):
            self.walk(child, locked)


def _collect_class(mod: Module, cls: ast.ClassDef) -> dict:
    is_thread_sub = any(tail(dotted(b)) == "Thread" for b in cls.bases)
    thread_targets: List[str] = []
    exempt: Set[str] = set()
    accesses: List[dict] = []
    methods = [n for n in cls.body
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for m in methods:
        for node in ast.walk(m):
            if isinstance(node, ast.Call) and \
                    tail(call_name(node)) == "Thread":
                for kw in node.keywords:
                    if kw.arg == "target":
                        attr = _self_attr(kw.value)
                        if attr is not None:
                            thread_targets.append(attr)
            if isinstance(node, (ast.Assign, ast.AnnAssign)) and \
                    isinstance(node.value, ast.Call) and \
                    tail(call_name(node.value)) in _THREAD_PRIMS:
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in targets:
                    attr = _self_attr(tgt)
                    if attr is not None:
                        exempt.add(attr)
    for m in methods:
        _AccessWalker(mod, m.name, accesses).walk(m, locked=False)
    return {
        "name": cls.name, "line": cls.lineno,
        "is_thread_subclass": is_thread_sub,
        "thread_targets": sorted(set(thread_targets)),
        "exempt_attrs": sorted(exempt),
        "accesses": accesses,
    }


def _module_constants(tree: ast.Module) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` string assignments."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, str):
            out[node.targets[0].id] = node.value.value
    return out


def _env_name(node: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def collect_facts(mod: Module) -> dict:
    """Extract one file's :data:`FileFacts` (a plain JSON-able dict)."""
    consts = _module_constants(mod.tree)

    def rec(node: ast.AST, **extra) -> dict:
        line = getattr(node, "lineno", 1)
        d = {"line": line, "symbol": mod.symbol_at(line),
             "snippet": mod.snippet_at(line)}
        d.update(extra)
        return d

    facts: dict = {
        "env_reads": [], "knob_literals": [], "knob_defs": [],
        "metric_names": [], "chaos_points": [], "chaos_site_defs": [],
        "chaos_site_refs": [], "classes": [],
        "dataflow": collect_dataflow(mod),
        "protocol": collect_protocol(mod),
        "suppressed": {str(k): sorted(v)
                       for k, v in mod.suppressed.items()},
    }

    # ---- context tags for metric-name string constants
    publish_ids: Set[int] = set()
    help_ids: Set[int] = set()
    consume_ids: Set[int] = set()

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            t = tail(call_name(node))
            str_args = [a for a in node.args
                        if isinstance(a, ast.Constant)
                        and isinstance(a.value, str)]
            str_args += [kw.value for kw in node.keywords
                         if kw.arg in ("metric", "name")
                         and isinstance(kw.value, ast.Constant)
                         and isinstance(kw.value.value, str)]
            if t in ("observe", "set_gauge", "inc"):
                publish_ids.update(id(a) for a in str_args)
            elif t == "series":
                consume_ids.update(id(a) for a in str_args)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            if isinstance(node.value, ast.Dict) and any(
                    isinstance(t, ast.Name) and "HELP" in t.id.upper()
                    for t in targets):
                help_ids.update(id(k) for k in node.value.keys
                                if k is not None)

    # ---- main literal / call sweep
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            value = node.value
            if KNOB_RE.fullmatch(value):
                facts["knob_literals"].append(rec(node, name=value))
            for nm in METRIC_RE.findall(value):
                if id(node) in help_ids or "# HELP" in value:
                    ctx = "help"
                elif id(node) in publish_ids or "# TYPE" in value:
                    ctx = "publish"
                elif id(node) in consume_ids:
                    ctx = "consume"
                else:
                    ctx = "other"
                facts["metric_names"].append(rec(node, name=nm,
                                                 context=ctx))
            continue
        if isinstance(node, ast.ClassDef):
            facts["classes"].append(_collect_class(mod, node))
            continue
        site_tgts = node.targets if isinstance(node, ast.Assign) \
            else [node.target] if isinstance(node, ast.AnnAssign) else []
        if len(site_tgts) == 1 and \
                isinstance(site_tgts[0], ast.Name) and \
                site_tgts[0].id == "SITES" and \
                isinstance(node.value, ast.Dict):
            for key in node.value.keys:
                if isinstance(key, ast.Constant) and \
                        isinstance(key.value, str):
                    facts["chaos_site_defs"].append(
                        rec(key, name=key.value))
            continue
        if isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load) and \
                dotted(node.value) in _ENVIRON:
            nm = _env_name(node.slice, consts)
            facts["env_reads"].append(rec(node, name=nm, how="subscript"))
            continue
        if isinstance(node, ast.Compare) and len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                len(node.comparators) == 1 and \
                dotted(node.comparators[0]) in _ENVIRON:
            nm = _env_name(node.left, consts)
            facts["env_reads"].append(rec(node, name=nm, how="membership"))
            continue
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node)
        t = tail(cn)
        first = node.args[0] if node.args else None
        first_str = (first.value if isinstance(first, ast.Constant)
                     and isinstance(first.value, str) else None)
        if (cn in _GETENV or
                (t == "get" and cn.rsplit(".", 1)[0] in _ENVIRON)):
            nm = _env_name(first, consts) if first is not None else None
            facts["env_reads"].append(rec(node, name=nm, how="get"))
        elif t == "_def" and first_str is not None:
            facts["knob_defs"].append(first_str)
        elif (t == "point" and ("chaos" in cn or cn == "point")
                or cn == "_chaos_point") and first_str is not None:
            facts["chaos_points"].append(rec(node, name=first_str))
        elif t == "add" and first_str is not None and \
                SITE_RE.fullmatch(first_str):
            facts["chaos_site_refs"].append(rec(node, name=first_str))
        elif t == "Fault":
            for kw in node.keywords:
                if kw.arg == "site" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    facts["chaos_site_refs"].append(
                        rec(node, name=kw.value.value))
    return facts


# ------------------------------------------------------------- native scan
_NATIVE_ENV_RE = re.compile(
    r'env_(?:double|int|bool|str)\s*\(\s*"(KFT_[A-Z0-9_]+)"')


def scan_native(root: Path) -> Dict[str, dict]:
    """Regex scan of native/src for ``env_*("KFT_...")`` reads; returns
    pseudo-facts entries so the knob-registry pass covers the C++
    transport's knobs too."""
    out: Dict[str, dict] = {}
    src = root / "native" / "src"
    if not src.is_dir():
        return out
    for fp in sorted(src.glob("*.cc")) + sorted(src.glob("*.h")):
        lits = []
        try:
            lines = fp.read_text(errors="replace").splitlines()
        except OSError:
            continue
        for i, text in enumerate(lines, start=1):
            for m in _NATIVE_ENV_RE.finditer(text):
                lits.append({"line": i, "symbol": "<native>",
                             "snippet": text.strip(),
                             "name": m.group(1)})
        if lits:
            rel = fp.relative_to(root).as_posix()
            out[rel] = {"env_reads": [], "knob_literals": lits,
                        "knob_defs": [], "metric_names": [],
                        "chaos_points": [], "chaos_site_defs": [],
                        "chaos_site_refs": [], "classes": [],
                        "dataflow": {}, "protocol": {},
                        "suppressed": {}}
    return out


# ------------------------------------------------------------------ cache
def _tool_hash() -> str:
    # the dataflow/protocol collectors feed facts["dataflow"] and
    # facts["protocol"], so their sources are part of the cache key too
    # (editing a protocol registry must invalidate stale facts)
    h = hashlib.md5(str(FACTS_SCHEMA).encode())
    h.update(Path(__file__).read_bytes())
    h.update((Path(__file__).parent / "dataflow.py").read_bytes())
    h.update((Path(__file__).parent / "protocol.py").read_bytes())
    return h.hexdigest()


class FactCache:
    """(mtime, size)-keyed facts, invalidated wholesale when this file
    changes.  Corrupt/missing cache files are treated as empty."""

    def __init__(self, path: Path = DEFAULT_CACHE):
        self.path = path
        self.tool = _tool_hash()
        self.files: Dict[str, dict] = {}
        self.dirty = False
        try:
            data = json.loads(path.read_text())
            if data.get("tool") == self.tool:
                self.files = data.get("files", {})
        except (OSError, ValueError):
            pass

    def get(self, rel: str, stat) -> Optional[dict]:
        e = self.files.get(rel)
        if e and e["mtime"] == stat.st_mtime and e["size"] == stat.st_size:
            return e["facts"]
        return None

    def put(self, rel: str, stat, facts: dict) -> None:
        self.files[rel] = {"mtime": stat.st_mtime, "size": stat.st_size,
                           "facts": facts}
        self.dirty = True

    def save(self) -> None:
        if not self.dirty:
            return
        try:
            self.path.write_text(json.dumps(
                {"tool": self.tool, "files": self.files}))
        except OSError:
            pass  # read-only checkout: run uncached


# ----------------------------------------------------------------- driver
def analyze(primary: Sequence[Path], context: Sequence[Path],
            rules: Sequence[Rule], root: Path, use_cache: bool = True,
            cache_path: Optional[Path] = None
            ) -> Tuple[List, Dict[str, dict], List[str]]:
    """Phase-1 walk: per-file rules over ``primary``, fact extraction
    over ``primary`` + ``context``.  Returns (rule_findings,
    facts_by_path, errors)."""
    findings: List = []
    errors: List[str] = []
    facts_by_path: Dict[str, dict] = {}
    cache = FactCache(cache_path or DEFAULT_CACHE) if use_cache else None
    seen: Set[str] = set()
    for group, run_rules in ((primary, True), (context, False)):
        for fp in iter_py_files(group, root):
            rel = fp.relative_to(root).as_posix() \
                if fp.is_relative_to(root) else fp.as_posix()
            if rel in seen:
                continue
            seen.add(rel)
            excluded = bool(PROGRAM_EXCLUDE.search(rel))
            try:
                st = fp.stat()
            except OSError as e:
                errors.append(f"{rel}: unreadable: {e}")
                continue
            cached = cache.get(rel, st) if cache else None
            if cached is not None and not run_rules:
                # context file, facts warm: no parse needed at all
                if not excluded:
                    facts_by_path[rel] = cached
                continue
            try:
                mod = Module(rel, fp.read_text())
            except (SyntaxError, UnicodeDecodeError, OSError) as e:
                errors.append(f"{rel}: unparseable: {e}")
                continue
            if run_rules:
                for rule in rules:
                    if not rule.applies_to(rel):
                        continue
                    for f in rule.check(mod):
                        if not mod.is_suppressed(f.rule, f.line):
                            findings.append(f)
            # primary files are parsed for the rules every run, but the
            # fact collectors (dataflow + protocol walks) are the
            # expensive half — serve those from the warm cache too
            fx = cached if cached is not None else collect_facts(mod)
            if cache and cached is None:
                cache.put(rel, st, fx)
            if not excluded:
                facts_by_path[rel] = fx
    if cache:
        cache.save()
    return findings, facts_by_path, errors
