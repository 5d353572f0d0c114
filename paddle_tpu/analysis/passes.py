"""Static analysis passes over the repo's own source (AST-level).

Four pass families, each enforcing a serving-stack invariant that is
otherwise only prose in a docstring:

* **trace-hazard** (`TraceHazardPass`) — inside every function handed
  to ``jax.jit`` (directly, through ``functools.partial``, or wrapped
  in `inference.serving._JitTracker`): Python control flow
  (``if``/``while``/ternary/``assert``) on a traced value,
  ``bool()``/``int()``/``float()`` coercions and ``.item()`` on traced
  values — each is a TracerBoolConversionError waiting for the first
  input that changes, or a silent per-call host sync.  Traced-ness is a
  taint walk seeded from the jitted function's positional parameters;
  keyword-only parameters bound by the wrapping ``partial`` (the repo's
  static-argument convention) and ``static_argnums``/``static_argnames``
  are static, and ``.shape``/``.dtype``-style attribute reads launder
  taint (shapes are trace-time constants).
* **flags-in-trace** (same pass) — ``FLAGS_*`` reads
  (``flags.flag(...)`` or ``FLAGS_x`` names) inside a traced function
  bake the flag value read at TRACE time into the executable; a later
  ``set_flags`` is silently ignored for cached signatures (the PR 1
  review-fix class).
* **lock-discipline** (`LockDisciplinePass`) — writes to the known
  shared registries (observability series, ``serving._STATS``,
  dispatch stats, the span buffer) must happen inside ``with <the
  designated lock>``.  Per-module `LockRule`s name the guarded roots,
  the lock spellings, and the alias edges (``s = _stats_for(op)``,
  ``for s in self._series.values()``) through which guarded state
  escapes into locals.
* **engine-mutation** (`EngineMutationPass`) — `DecodeEngine` is
  single-threaded by contract: every mutation happens between steps on
  the driver.  Calls of mutating engine methods (and attribute stores
  on an engine receiver) outside the sanctioned between-steps sites
  are findings.
* **donation** (`DonationPass`) — every ``jax.jit`` site is
  cross-checked: a jitted function that takes the KV pool (its ``kv``
  parameter: one `ops.pallas.paged_attention.KVPool`, pages and quant
  scales together) must have it in ``donate_argnums`` (a missed
  donation means a full extra copy of the KV pool per step).
* **fleet-trace** (`FleetTracePass`) — every HTTP site under the
  fleet plane (a client leg calling ``urlopen``, or a ``do_*``
  server-handler method) must carry the fleet trace: reference the
  ``x-paddle-trace`` plumbing (``fleettrace`` / ``TRACE_HEADER`` /
  the literal header string) in its body or same-module call
  closure, or sit on the explicit allowlist of control-plane
  endpoints with no request identity — so a new fleet endpoint
  cannot silently drop the trace (docs/FLEET_TRACING.md).

Findings carry a content-addressed ``fingerprint`` (pass id + file +
source line text, no line number) so the baseline grandfather file
survives unrelated edits but resurfaces the moment the offending line
changes.  A line ending in ``# tracecheck: ok`` is suppressed — for
the rare deliberate exception; prefer fixing.

All passes are pure ``ast`` — no jax import, no execution of the
scanned code.
"""
from __future__ import annotations

import ast
import hashlib
import os
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Finding", "SourceModule", "LockRule", "EngineRule",
    "FleetTraceRule", "scan_paths",
    "TraceHazardPass", "LockDisciplinePass", "EngineMutationPass",
    "DonationPass", "FleetTracePass", "run_passes",
]


# ---------------------------------------------------------------------------
# Findings
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Finding:
    pass_id: str   # trace-hazard | flags-in-trace | lock-discipline |
    #              # engine-mutation | donation
    path: str      # repo-relative, forward slashes
    line: int
    message: str
    snippet: str = ""
    # occurrence index among findings with identical (pass, path,
    # snippet) — assigned by `run_passes` in line order, so a NEWLY
    # duplicated copy of a baselined bad line gets a fresh fingerprint
    # instead of silently riding the grandfather entry
    ordinal: int = 0

    @property
    def fingerprint(self) -> str:
        """Content-addressed id for the baseline: stable across line-
        number drift, invalidated when the offending line's text (or
        the message, for file-level findings) changes or when a new
        duplicate of the same line appears."""
        h = hashlib.sha1()
        key = f"{self.pass_id}|{self.path}|{self.snippet or self.message}"
        if self.ordinal:
            key += f"|#{self.ordinal}"
        h.update(key.encode())
        return h.hexdigest()[:16]

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.pass_id}] "
                f"{self.message}")


class SourceModule:
    """One parsed source file plus the line-level suppression map."""

    def __init__(self, abspath: str, relpath: str):
        self.abspath = abspath
        self.relpath = relpath.replace(os.sep, "/")
        with open(abspath, "r", encoding="utf-8") as f:
            self.source = f.read()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=relpath)
        # every FunctionDef/Lambda in the module (nested included),
        # name -> node; the jit-site resolver consults this first and
        # the cross-module index second
        self.functions: Dict[str, ast.AST] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.setdefault(node.name, node)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, lineno: int) -> bool:
        return "# tracecheck: ok" in self.line_text(lineno)

    def finding(self, pass_id: str, node: ast.AST, message: str
                ) -> Optional[Finding]:
        line = getattr(node, "lineno", 0)
        if self.suppressed(line):
            return None
        return Finding(pass_id, self.relpath, line, message,
                       snippet=self.line_text(line))


def scan_paths(paths: Sequence[str], repo_root: str) -> List[SourceModule]:
    """Parse every ``.py`` file under ``paths`` (files or directories,
    absolute or repo-root-relative), sorted for determinism."""
    files = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(repo_root, p)
        if os.path.isfile(ap):
            files.append(ap)
        elif os.path.isdir(ap):
            for dirpath, _dirs, names in os.walk(ap):
                if "__pycache__" in dirpath:
                    continue
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(dirpath, n))
        else:
            raise FileNotFoundError(f"no such path: {p}")
    mods = []
    for ap in sorted(dict.fromkeys(files)):
        rel = os.path.relpath(ap, repo_root)
        mods.append(SourceModule(ap, rel))
    return mods


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------
def _dotted(node: ast.AST) -> Optional[str]:
    """`a.b.c` -> "a.b.c" for Name/Attribute chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return None if base is None else f"{base}.{node.attr}"
    return None


def _qualname_walk(tree: ast.AST):
    """Yield (qualname, FunctionDef) for every function in the module,
    with ``Class.method`` / ``outer.<locals>.inner`` qualnames."""
    def rec(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                yield q, child
                yield from rec(child, f"{q}.<locals>.")
            elif isinstance(child, ast.ClassDef):
                yield from rec(child, f"{prefix}{child.name}.")
            else:
                yield from rec(child, prefix)
    yield from rec(tree, "")


# ---------------------------------------------------------------------------
# jit-site collection (shared by the trace and donation passes)
# ---------------------------------------------------------------------------
@dataclass
class JitSite:
    call: ast.Call            # the jax.jit(...) call
    module: SourceModule
    fn_node: Optional[ast.AST]        # resolved FunctionDef / Lambda
    fn_name: str
    static_names: Tuple[str, ...]     # partial kwargs + static_argnames
    static_argnums: Tuple[int, ...]
    pos_shift: int                    # partial positional args bound
    donate_argnums: Optional[Tuple[int, ...]]  # None = kwarg absent


def _is_jax_jit(func: ast.AST) -> bool:
    d = _dotted(func)
    return d is not None and (d == "jax.jit" or d.endswith(".jax.jit")
                              or d == "jit")


def _is_jit_tracker(func: ast.AST) -> bool:
    # inference.serving._JitTracker owns its own jax.jit (single source
    # of truth for donate_argnums), so a tracker construction over a
    # plain callable IS a jit site
    d = _dotted(func)
    return d is not None and d.split(".")[-1] == "_JitTracker"


def _literal_ints(node: ast.AST) -> Optional[Tuple[int, ...]]:
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None
    if isinstance(v, int):
        return (v,)
    if isinstance(v, (tuple, list)) and all(isinstance(x, int) for x in v):
        return tuple(v)
    return None


def collect_jit_sites(modules: Sequence[SourceModule]) -> List[JitSite]:
    index: Dict[str, Tuple[SourceModule, ast.AST]] = {}
    for m in modules:
        for name, node in m.functions.items():
            index.setdefault(name, (m, node))
    sites = []
    for m in modules:
        for node in ast.walk(m.tree):
            if not (isinstance(node, ast.Call) and node.args
                    and (_is_jax_jit(node.func)
                         or _is_jit_tracker(node.func))):
                continue
            fn_expr = node.args[0]
            if _is_jit_tracker(node.func) and \
                    isinstance(fn_expr, ast.Call) and \
                    _is_jax_jit(fn_expr.func):
                continue  # tracker over an explicit jax.jit: the inner
                #         # call is collected as its own site
            static_names: List[str] = []
            static_argnums: Tuple[int, ...] = ()
            pos_shift = 0
            donate = None
            for kw in node.keywords:
                if kw.arg == "donate_argnums":
                    donate = _literal_ints(kw.value)
                elif kw.arg == "static_argnums":
                    static_argnums = _literal_ints(kw.value) or ()
                elif kw.arg == "static_argnames":
                    try:
                        v = ast.literal_eval(kw.value)
                        static_names.extend(
                            [v] if isinstance(v, str) else list(v))
                    except (ValueError, SyntaxError):
                        pass
            # unwrap functools.partial(fn, *bound, **statics)
            target = fn_expr
            if isinstance(target, ast.Call) and \
                    (_dotted(target.func) or "").endswith("partial") and \
                    target.args:
                pos_shift = len(target.args) - 1
                static_names.extend(
                    kw.arg for kw in target.keywords if kw.arg)
                target = target.args[0]
            fn_node = None
            fn_name = "<unknown>"
            if isinstance(target, ast.Lambda):
                fn_node, fn_name = target, "<lambda>"
            else:
                d = _dotted(target)
                if d is not None:
                    fn_name = d.split(".")[-1]
                    if fn_name in m.functions:
                        fn_node = m.functions[fn_name]
                    elif fn_name in index:
                        fn_node = index[fn_name][1]
            sites.append(JitSite(node, m, fn_node, fn_name,
                                 tuple(static_names), static_argnums,
                                 pos_shift, donate))
    return sites


# ---------------------------------------------------------------------------
# trace-hazard + flags-in-trace
# ---------------------------------------------------------------------------
# attribute reads that launder taint: trace-time constants of a traced
# array
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "weak_type", "sharding"}
# len() is deliberately absent: on a traced array of known rank it
# returns the STATIC shape[0] — legal jax, no sync, no trace error
_COERCIONS = {"bool", "int", "float", "complex"}


class TraceHazardPass:
    """Hazard walk over every resolved jit-site function body."""

    def run(self, modules: Sequence[SourceModule],
            sites: Optional[List[JitSite]] = None) -> List[Finding]:
        out: List[Finding] = []
        seen = set()
        for site in sites if sites is not None \
                else collect_jit_sites(modules):
            fn = site.fn_node
            if fn is None:
                continue
            # dedup on the EFFECTIVE trace config, not just the def:
            # the same function jitted twice with different static
            # bindings has different traced parameter sets, and each
            # must be analyzed
            key = (site.module.relpath, id(fn), site.static_names,
                   site.static_argnums, site.pos_shift)
            if key in seen:
                continue
            seen.add(key)
            out.extend(self._check_fn(site))
        return [f for f in out if f is not None]

    def _check_fn(self, site: JitSite) -> List[Finding]:
        fn = site.fn_node
        mod = site.module
        args = fn.args
        tainted = set()
        params = [a.arg for a in getattr(args, "posonlyargs", [])] + \
            [a.arg for a in args.args]
        for i, name in enumerate(params):
            if name in site.static_names:
                continue
            # static_argnums index the JITTED signature: def param i is
            # jit argument i - (partial-bound positional count)
            if (i - site.pos_shift) in site.static_argnums:
                continue
            if name == "self":
                continue
            tainted.add(name)
        if args.vararg is not None:
            tainted.add(args.vararg.arg)
        for a in args.kwonlyargs:
            # keyword-only params bound by the wrapping partial (or
            # named in static_argnames) are static; the rest are traced
            # runtime kwargs
            if a.arg not in site.static_names:
                tainted.add(a.arg)

        findings: List[Finding] = []

        def is_tainted(e: ast.AST) -> bool:
            if isinstance(e, ast.Name):
                return e.id in tainted
            if isinstance(e, ast.Attribute):
                if e.attr in _STATIC_ATTRS:
                    return False
                return is_tainted(e.value)
            if isinstance(e, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return False
            for child in ast.iter_child_nodes(e):
                if is_tainted(child):
                    return True
            return False

        def taint_target(t: ast.AST):
            if isinstance(t, ast.Name):
                tainted.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    taint_target(e)
            elif isinstance(t, ast.Starred):
                taint_target(t.value)
            elif isinstance(t, ast.Subscript):
                # storing a traced value into a container taints the
                # container (vals[p] = traced_v)
                taint_target(t.value)
            # attribute stores on locals: ignore (rare in pure fns)

        def flag_read(call: ast.Call) -> bool:
            d = _dotted(call.func)
            return d is not None and (d == "flag" or d.endswith(".flag"))

        def visit(node: ast.AST):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not fn:
                return  # nested defs have their own scope/trace story
            if isinstance(node, (ast.If, ast.While)) and \
                    is_tainted(node.test):
                findings.append(mod.finding(
                    "trace-hazard", node,
                    f"python `{type(node).__name__.lower()}` on a traced "
                    f"value inside jitted `{site.fn_name}` — branch on "
                    f"host data or use lax.cond/jnp.where"))
            elif isinstance(node, ast.IfExp) and is_tainted(node.test):
                findings.append(mod.finding(
                    "trace-hazard", node,
                    f"conditional expression on a traced value inside "
                    f"jitted `{site.fn_name}` — use jnp.where"))
            elif isinstance(node, ast.Assert) and is_tainted(node.test):
                findings.append(mod.finding(
                    "trace-hazard", node,
                    f"assert on a traced value inside jitted "
                    f"`{site.fn_name}` — traced assertions do not run; "
                    f"use checkify or move the check to the host"))
            elif isinstance(node, ast.comprehension):
                for cond in node.ifs:
                    if is_tainted(cond):
                        findings.append(mod.finding(
                            "trace-hazard", cond,
                            f"comprehension filter on a traced value "
                            f"inside jitted `{site.fn_name}`"))
            elif isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in _COERCIONS and any(is_tainted(a)
                                           for a in node.args):
                    findings.append(mod.finding(
                        "trace-hazard", node,
                        f"host coercion `{d}()` on a traced value inside "
                        f"jitted `{site.fn_name}` — forces a trace error "
                        f"or a per-call host sync"))
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and \
                        is_tainted(node.func.value):
                    findings.append(mod.finding(
                        "trace-hazard", node,
                        f"`.item()` on a traced value inside jitted "
                        f"`{site.fn_name}` — blocking host sync"))
                elif flag_read(node):
                    findings.append(mod.finding(
                        "flags-in-trace", node,
                        f"flag read inside jitted `{site.fn_name}` bakes "
                        f"the trace-time value into the executable — "
                        f"read the flag on the host and pass it in (or "
                        f"key the executable cache on it)"))
            elif isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load) and \
                    node.id.startswith("FLAGS_"):
                findings.append(mod.finding(
                    "flags-in-trace", node,
                    f"FLAGS read `{node.id}` inside jitted "
                    f"`{site.fn_name}` bakes the trace-time value into "
                    f"the executable"))
            # statement-order taint propagation
            if isinstance(node, ast.Assign):
                if is_tainted(node.value):
                    for t in node.targets:
                        taint_target(t)
            elif isinstance(node, ast.AugAssign):
                if is_tainted(node.value):
                    taint_target(node.target)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if is_tainted(node.value):
                    taint_target(node.target)
            elif isinstance(node, ast.For):
                if is_tainted(node.iter):
                    taint_target(node.target)
            elif isinstance(node, (ast.NamedExpr,)):
                if is_tainted(node.value):
                    taint_target(node.target)
            for child in ast.iter_child_nodes(node):
                visit(child)

        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            visit(stmt)
        return findings


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------
_MUTATORS = {
    "append", "appendleft", "add", "clear", "discard", "extend",
    "insert", "pop", "popitem", "popleft", "remove", "setdefault",
    "update", "move_to_end", "sort", "reverse",
}


@dataclass
class LockRule:
    """Per-module lock-discipline spec: which names are the guarded
    shared registries, which lock spellings guard them, and how guarded
    state aliases into locals."""

    locks: Tuple[str, ...]             # acceptable `with X:` spellings
    roots: Tuple[str, ...] = ()        # module-global registry names
    self_attrs: Tuple[str, ...] = ()   # guarded `self.<attr>` state
    alias_fns: Tuple[str, ...] = ()    # x = alias_fn(...) taints x
    alias_attrs: Tuple[str, ...] = ()  # x = y.<attr> taints x
    guarded_classes: Tuple[str, ...] = ()  # self.<any> writes in these
    #                                  # classes must be locked
    exempt: Tuple[str, ...] = ()       # exempt function qualnames


class LockDisciplinePass:
    def __init__(self, rules: Dict[str, LockRule]):
        # rules keyed by module-path suffix ("observability/metrics.py")
        self.rules = rules

    def _rule_for(self, relpath: str) -> Optional[LockRule]:
        for suffix, rule in self.rules.items():
            if relpath.endswith(suffix):
                return rule
        return None

    def run(self, modules: Sequence[SourceModule]) -> List[Finding]:
        out: List[Finding] = []
        for m in modules:
            rule = self._rule_for(m.relpath)
            if rule is None:
                continue
            for qualname, fn in _qualname_walk(m.tree):
                if qualname in rule.exempt or \
                        qualname.endswith("__init__"):
                    continue
                out.extend(self._check_fn(m, rule, qualname, fn))
        return [f for f in out if f is not None]

    def _check_fn(self, mod: SourceModule, rule: LockRule,
                  qualname: str, fn: ast.AST) -> List[Finding]:
        findings: List[Finding] = []
        aliases = set()
        in_guarded_class = any(qualname.startswith(c + ".")
                               for c in rule.guarded_classes)

        def guarded(e: ast.AST) -> bool:
            """Does this expression reach guarded shared state?"""
            if isinstance(e, ast.Name):
                return e.id in rule.roots or e.id in aliases
            if isinstance(e, ast.Attribute):
                if isinstance(e.value, ast.Name) and \
                        e.value.id == "self" and e.attr in rule.self_attrs:
                    return True
                if e.attr in rule.alias_attrs:
                    return True
                return guarded(e.value)
            if isinstance(e, ast.Call):
                d = _dotted(e.func)
                if d is not None and d.split(".")[-1] in rule.alias_fns:
                    return True
                return guarded(e.func)
            if isinstance(e, ast.Subscript):
                return guarded(e.value)
            return False

        def lock_expr(item: ast.AST) -> bool:
            d = _dotted(item)
            return d is not None and (
                d in rule.locks or d.split(".")[-1] in rule.locks)

        def report(node, what):
            findings.append(mod.finding(
                "lock-discipline", node,
                f"{what} outside `with "
                f"{'/'.join(rule.locks)}` in `{qualname}` — shared "
                f"telemetry state must only be written under its "
                f"designated lock"))

        def visit(node: ast.AST, locked: bool):
            if isinstance(node, ast.With):
                inner = locked or any(lock_expr(i.context_expr)
                                      for i in node.items)
                for child in node.body:
                    visit(child, inner)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not fn:
                return  # nested defs get their own qualname walk
            # alias propagation (runs regardless of lock state: an
            # alias taken under the lock can leak out of it)
            if isinstance(node, ast.Assign) and guarded(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        aliases.add(t.id)
            elif isinstance(node, ast.For) and guarded(node.iter):
                if isinstance(node.target, ast.Name):
                    aliases.add(node.target.id)
                elif isinstance(node.target, ast.Tuple):
                    for e in node.target.elts:
                        if isinstance(e, ast.Name):
                            aliases.add(e.id)
            if not locked:
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        if isinstance(t, ast.Name) and t.id in rule.roots:
                            report(node, f"rebinding of shared registry "
                                         f"`{t.id}`")
                        elif isinstance(t, ast.Subscript) and \
                                guarded(t.value):
                            report(node, "item write to shared registry "
                                         "state")
                        elif isinstance(t, ast.Attribute):
                            if guarded(t.value):
                                report(node, "attribute write to shared "
                                             "registry state")
                            elif in_guarded_class and \
                                    isinstance(t.value, ast.Name) and \
                                    t.value.id == "self":
                                report(node, "unlocked mutation of "
                                             "lock-guarded object state")
                        elif isinstance(t, ast.Tuple):
                            for e in t.elts:
                                if isinstance(e, ast.Name) and \
                                        e.id in rule.roots:
                                    report(node, f"rebinding of shared "
                                                 f"registry `{e.id}`")
                elif isinstance(node, ast.Delete):
                    for t in node.targets:
                        if isinstance(t, ast.Subscript) and \
                                guarded(t.value):
                            report(node, "item delete on shared registry "
                                         "state")
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _MUTATORS and \
                        guarded(node.func.value):
                    report(node, f"mutating call "
                                 f"`.{node.func.attr}()` on shared "
                                 f"registry state")
            for child in ast.iter_child_nodes(node):
                visit(child, locked)

        for stmt in fn.body:
            visit(stmt, False)
        return findings


# ---------------------------------------------------------------------------
# engine-mutation discipline
# ---------------------------------------------------------------------------
@dataclass
class EngineRule:
    """Which methods mutate a DecodeEngine, which receiver spellings
    count as "an engine", and which (module-suffix -> qualname
    prefixes) sites are sanctioned between-steps callers ("*" = the
    whole module)."""

    mutators: Tuple[str, ...]
    receivers: Tuple[str, ...] = ("eng", "engine", "self.engine",
                                  "self._engine")
    sanctioned: Dict[str, Tuple[str, ...]] = field(default_factory=dict)


class EngineMutationPass:
    def __init__(self, rule: EngineRule):
        self.rule = rule

    def _sanctioned(self, relpath: str, qualname: str) -> bool:
        for suffix, prefixes in self.rule.sanctioned.items():
            if relpath.endswith(suffix):
                if "*" in prefixes:
                    return True
                return any(qualname == p or qualname.startswith(p)
                           for p in prefixes)
        return False

    @staticmethod
    def _own_nodes(fn: ast.AST):
        """Every node lexically inside ``fn`` but NOT inside a nested
        def (those are analyzed under their own qualname)."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def run(self, modules: Sequence[SourceModule]) -> List[Finding]:
        out: List[Finding] = []
        rule = self.rule
        for m in modules:
            for qualname, fn in _qualname_walk(m.tree):
                if self._sanctioned(m.relpath, qualname):
                    continue
                for node in self._own_nodes(fn):
                    if isinstance(node, ast.Call) and \
                            isinstance(node.func, ast.Attribute) and \
                            node.func.attr in rule.mutators and \
                            _dotted(node.func.value) in rule.receivers:
                        f = m.finding(
                            "engine-mutation", node,
                            f"engine-mutating call "
                            f"`.{node.func.attr}()` from unsanctioned "
                            f"site `{qualname}` — all engine mutation "
                            f"must happen between steps on the driver "
                            f"(see inference/frontend.py)")
                        if f:
                            out.append(f)
                    elif isinstance(node, ast.Assign):
                        for t in node.targets:
                            if isinstance(t, ast.Attribute) and \
                                    _dotted(t.value) in rule.receivers:
                                f = m.finding(
                                    "engine-mutation", node,
                                    f"engine attribute store "
                                    f"`.{t.attr} = ...` from "
                                    f"unsanctioned site `{qualname}`")
                                if f:
                                    out.append(f)
        return out


# ---------------------------------------------------------------------------
# donation coverage
# ---------------------------------------------------------------------------
class DonationPass:
    """Every jax.jit site whose function takes the KV pool — the
    parameter named ``kv``, as every step function of the serving
    stack names its `KVPool` — must donate it.  The pool is one
    argument whatever it holds (pages, and the quant scales of an int8
    pool), so a site cannot donate half of it."""

    POOL_PARAM = "kv"

    def run(self, modules: Sequence[SourceModule],
            sites: Optional[List[JitSite]] = None) -> List[Finding]:
        out: List[Finding] = []
        for site in sites if sites is not None \
                else collect_jit_sites(modules):
            fn = site.fn_node
            if fn is None or isinstance(fn, ast.Lambda):
                continue
            args = fn.args
            params = [a.arg for a in getattr(args, "posonlyargs", [])] + \
                [a.arg for a in args.args]
            if self.POOL_PARAM not in params:
                continue
            jit_idx = params.index(self.POOL_PARAM) - site.pos_shift
            if jit_idx < 0:
                continue  # bound by partial positionally: not a jit
                #         # argument at all
            if jit_idx not in set(site.donate_argnums or ()):
                f = site.module.finding(
                    "donation", site.call,
                    f"jax.jit of `{site.fn_name}` does not donate "
                    f"pool parameter `{self.POOL_PARAM}` (argnum "
                    f"{jit_idx}) — add it to donate_argnums or the "
                    f"step pays a full extra copy of the KV pool"
                    + ("" if site.donate_argnums is not None
                       else " (no donate_argnums at all)"))
                if f:
                    out.append(f)
        return out


# ---------------------------------------------------------------------------
# fleet-trace propagation
# ---------------------------------------------------------------------------
@dataclass
class FleetTraceRule:
    """Which modules are the fleet's network plane, which spellings
    count as carrying the trace, and which HTTP sites are exempt
    (control-plane endpoints with no request identity)."""

    path_markers: Tuple[str, ...] = ("fleet/",)   # relpath substring
    trace_names: Tuple[str, ...] = ("fleettrace", "TRACE_HEADER")
    trace_literal: str = "x-paddle-trace"
    allowlist: Tuple[str, ...] = ()               # exact qualnames


class FleetTracePass:
    """Every HTTP site under the fleet plane must carry the fleet
    trace (docs/FLEET_TRACING.md): a client leg (any function calling
    ``urlopen``) or a server handler (``do_*`` method) either
    references the trace plumbing — ``fleettrace``, ``TRACE_HEADER``,
    or the literal ``x-paddle-trace`` string — in its body or its
    same-module call closure, or sits on the explicit allowlist.  A
    new fleet endpoint that silently drops the trace flags the moment
    it is written."""

    def __init__(self, rule: FleetTraceRule):
        self.rule = rule

    def _in_scope(self, relpath: str) -> bool:
        return any(mark in relpath for mark in self.rule.path_markers)

    @staticmethod
    def _site_kind(fn: ast.AST) -> Optional[str]:
        if getattr(fn, "name", "").startswith("do_"):
            return "HTTP handler"
        for node in EngineMutationPass._own_nodes(fn):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d is not None and d.split(".")[-1] == "urlopen":
                    return "HTTP client leg"
        return None

    def _carries_trace(self, fn: ast.AST, mod: SourceModule,
                       visited: Optional[set] = None) -> bool:
        """Body or same-module transitive call closure references the
        trace plumbing.  The closure walk matters: ``do_POST``
        dispatches to ``_generate`` which reads the header — the
        handler itself never spells the name."""
        if visited is None:
            visited = set()
        if id(fn) in visited:
            return False
        visited.add(id(fn))
        called: List[str] = []
        for node in EngineMutationPass._own_nodes(fn):
            if isinstance(node, ast.Name) and \
                    node.id in self.rule.trace_names:
                return True
            if isinstance(node, ast.Attribute) and \
                    node.attr in self.rule.trace_names:
                return True
            if isinstance(node, ast.Constant) and \
                    node.value == self.rule.trace_literal:
                return True
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d is not None:
                    called.append(d.split(".")[-1])
        for name in called:
            target = mod.functions.get(name)
            if target is not None and \
                    self._carries_trace(target, mod, visited):
                return True
        return False

    def run(self, modules: Sequence[SourceModule]) -> List[Finding]:
        out: List[Finding] = []
        for m in modules:
            if not self._in_scope(m.relpath):
                continue
            for qualname, fn in _qualname_walk(m.tree):
                kind = self._site_kind(fn)
                if kind is None:
                    continue
                if qualname in self.rule.allowlist:
                    continue
                if self._carries_trace(fn, m):
                    continue
                f = m.finding(
                    "fleet-trace", fn,
                    f"{kind} `{qualname}` neither propagates the fleet "
                    f"trace (x-paddle-trace / fleettrace.TRACE_HEADER) "
                    f"nor sits on the control-plane allowlist — fleet "
                    f"HTTP surfaces must carry the trace or be "
                    f"explicitly exempted (docs/FLEET_TRACING.md)")
                if f:
                    out.append(f)
        return out


# ---------------------------------------------------------------------------
# combined runner
# ---------------------------------------------------------------------------
def run_passes(modules: Sequence[SourceModule],
               lock_rules: Optional[Dict[str, LockRule]] = None,
               engine_rule: Optional[EngineRule] = None,
               fleet_rule: Optional[FleetTraceRule] = None
               ) -> List[Finding]:
    findings: List[Finding] = []
    sites = collect_jit_sites(modules)  # shared: one AST walk, 2 users
    findings.extend(TraceHazardPass().run(modules, sites))
    if lock_rules:
        findings.extend(LockDisciplinePass(lock_rules).run(modules))
    if engine_rule:
        findings.extend(EngineMutationPass(engine_rule).run(modules))
    findings.extend(DonationPass().run(modules, sites))
    if fleet_rule:
        findings.extend(FleetTracePass(fleet_rule).run(modules))
    findings.sort(key=lambda f: (f.path, f.line, f.pass_id))
    seen: Dict[tuple, int] = {}
    out = []
    for f in findings:
        key = (f.pass_id, f.path, f.snippet or f.message)
        n = seen.get(key, 0)
        seen[key] = n + 1
        out.append(replace(f, ordinal=n) if n else f)
    return out
