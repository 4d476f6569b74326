"""Whole-program concurrency index: interprocedural call graph +
canonical lock identities + lock regions.

The five original vnlint rules are lexical — every concurrency bug this
repo shipped (PR-1 donation race, PR-3 pin leak, PR-6 closed-channel
accounting gap) crossed a function boundary they cannot see.  This
module is the shared substrate the interprocedural rules (lock-order,
blocking-propagation) and the runtime lock-witness comparator build on:

  1. a symbol index over the whole package — classes (incl. nested),
     methods, module functions, with best-effort type inference for
     `self.x` attributes (constructor calls, annotations, known
     parameter names) and locals (assignments, parameter annotations,
     return annotations like `-> "PendingFlush"`);
  2. CANONICAL LOCK IDENTITIES: every `threading.Lock/RLock/Condition`
     bound to an attribute or module global gets one stable name —
     `MetricAggregator.lock`, `Server._flush_serial`,
     `Destinations._lock`, `failpoints._lock`, `_ArenaBase.lock` (the
     arena lock is named for the class that ASSIGNS it, so every arena
     family shares one identity).  `Condition(self._lock)` aliases to
     the wrapped lock's identity.  The runtime witness
     (analysis/witness.py) uses the SAME names, which is what makes
     static-vs-observed edges comparable at all;
  3. per-function lock regions: `with <lock>:` blocks, bare
     `lock.acquire()` (held to end of function; a lexically unmatched
     acquire marks the function as RETURNING WITH THE LOCK HELD, and
     callers extend their held set across the call — the
     `reshard_begin`/`reshard_commit` window), and the `*_locked`
     naming convention (body runs with the CALLER's lock; modeled as a
     pseudo-lock so intra-function rules fire even without a caller in
     the analyzed tree);
  4. call resolution: `self.m()`, `self.attr.m()` via attr types,
     typed locals, module functions, `serving.x` cross-module forms,
     constructors (incl. `with Ctor():` entering `__enter__`/
     `__exit__`), callback attributes bound at construction sites
     (`Destinations(handoff=self._reshard_handoff)`), and a
     unique-method fallback for names defined exactly once
     project-wide (generic names blocklisted);
  5. derived analyses: BLOCKING REACHABILITY (a function that reaches
     `.result()` / `time.sleep` / a device sync through any call chain
     is blocking — lockguard's table, made transitive) and the
     ACQUIRED-WHILE-HOLDING GRAPH whose cycles are potential
     deadlocks, each edge carrying a witness chain (holder function,
     call chain, acquisition site).

Everything here is deterministic: iteration orders are sorted, chains
prefer the first (shortest-first) discovery, and the exported graph
(`to_graph_dict`) is byte-stable across runs for the committed
artifact.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Optional

from veneur_tpu.analysis import astutil

# pseudo-lock prefix for the `*_locked` convention: the body runs with
# the caller's lock held, but which one is the caller's business — the
# pseudo entry makes held-set rules fire inside the function itself
# while staying OUT of the lock-order graph (callers contribute the
# real identity through the call chain).
CONVENTION_PREFIX = "*"

_LOCK_CTOR_NAMES = {"Lock", "RLock", "Condition"}

# receiver/parameter names whose project type is unambiguous by
# convention; used only when no stronger evidence (annotation,
# constructor call) exists
_PARAM_TYPE_HINTS = {
    "agg": "MetricAggregator",
    "aggregator": "MetricAggregator",
    "server": "Server",
    "srv": "Server",
    "proxy": "Proxy",
}

# method names too generic for the unique-definition fallback: a
# project-unique `def get` is far more likely to collide with dicts,
# sockets and numpy than to be the real callee
_GENERIC_METHOD_NAMES = {
    "get", "put", "close", "open", "start", "stop", "run", "send",
    "recv", "read", "write", "wait", "join", "items", "keys",
    "values", "append", "extend", "pop", "popleft", "add", "update",
    "clear", "copy", "acquire", "release", "submit", "result", "set",
    "sum", "mean", "min", "max", "count", "index", "insert", "remove",
    "sort", "format", "split", "strip", "encode", "decode", "lower",
    "upper", "startswith", "endswith", "tolist", "astype", "reshape",
    "ravel", "view", "any", "all", "nonzero", "cumsum", "fileno",
    "sendto", "recvfrom", "bind", "listen", "accept", "connect",
    "group", "match", "search", "sub", "findall", "exists", "mkdir",
    "is_set", "locked", "empty", "full", "qsize", "get_nowait",
    "put_nowait", "cancel", "done", "flush",
}

_MAX_CHAIN_DEPTH = 8


@dataclass
class Acquisition:
    lock: str
    line: int
    # locks already held when this acquisition happens (lexically
    # within the same function), innermost last; pseudo-locks included
    held: tuple[tuple[str, int], ...]


@dataclass
class CallSite:
    text: str                  # dotted call text ("self.agg.flush")
    line: int
    col: int
    held: tuple[tuple[str, int], ...]
    callees: tuple["FunctionInfo", ...] = ()


@dataclass
class FunctionInfo:
    qname: str                 # "Server.flush" / "failpoints.inject"
    name: str
    relpath: str
    module_stem: str
    node: ast.AST
    cls: Optional["ClassInfo"] = None
    acquisitions: list[Acquisition] = field(default_factory=list)
    calls: list[CallSite] = field(default_factory=list)
    # direct blocking ops (lockguard's table): (label, line)
    blocking_direct: list[tuple[str, int]] = field(default_factory=list)
    # canonical locks this function acquires/releases WITHOUT a
    # balancing counterpart in its own body (reshard_begin/commit)
    leaves_held: tuple[str, ...] = ()
    releases: tuple[str, ...] = ()


@dataclass
class ClassInfo:
    name: str
    qname: str                 # nested classes: "Outer._CompileGuard"
    relpath: str
    module_stem: str
    bases: list[str] = field(default_factory=list)
    methods: dict[str, FunctionInfo] = field(default_factory=dict)
    nested: dict[str, "ClassInfo"] = field(default_factory=dict)
    attr_locks: dict[str, str] = field(default_factory=dict)
    attr_types: dict[str, str] = field(default_factory=dict)
    # callback attributes: attr -> candidate methods bound at
    # construction sites ("Destinations(handoff=self._reshard_handoff)")
    attr_callables: dict[str, list[FunctionInfo]] = field(
        default_factory=dict)
    # __init__ parameters assigned verbatim to self.<attr>
    ctor_param_attrs: dict[str, str] = field(default_factory=dict)


def _ann_type_name(node) -> Optional[str]:
    """Best-effort class name from an annotation / ctor expression:
    `Server`, `"PendingFlush"`, `Optional[Proxy]`, `mod.Cls`."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.split("[")[0].split(".")[-1].strip("\"' ")
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Subscript):
        # Optional[X] / list[X]: the Optional case is the useful one
        base = astutil.dotted(node.value) or ""
        if base.rsplit(".", 1)[-1] == "Optional":
            return _ann_type_name(node.slice)
    return None


def _lock_ctor(call: ast.Call) -> bool:
    name = astutil.call_func_name(call) or ""
    return name.rsplit(".", 1)[-1] in _LOCK_CTOR_NAMES


class ConcurrencyIndex:
    """Built once per lint run (cached on the ProjectContext) and
    shared by every interprocedural rule."""

    def __init__(self):
        self.classes: dict[str, list[ClassInfo]] = {}   # simple name
        self.functions: list[FunctionInfo] = []
        # (stem, fname) -> FunctionInfo for module-level functions
        self.module_funcs: dict[tuple[str, str], FunctionInfo] = {}
        # stem -> {global name -> canonical lock id}
        self.module_locks: dict[str, dict[str, str]] = {}
        self.methods_by_name: dict[str, list[FunctionInfo]] = {}
        self._reach_memo: dict[int, dict] = {}
        self._block_memo: dict[int, Optional[tuple]] = {}
        self._env_memo: dict[int, dict] = {}
        # bumped whenever a reach/blocking traversal bails on a cycle
        # or the depth cap: results computed under truncation are
        # INCOMPLETE and must not be memoized (a poisoned memo would
        # silently drop edges for every later caller)
        self._truncations = 0
        self.unresolved_calls = 0

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, modules) -> "ConcurrencyIndex":
        idx = cls()
        for mod in modules:
            idx._index_module(mod)
        for mod in modules:
            idx._index_class_attrs(mod)
        # callback bindings need attr/ctor info, so third pass
        for mod in modules:
            idx._index_callback_bindings(mod)
        for fn in idx.functions:
            idx._scan_explicit_acquires(fn)
        for fn in idx.functions:
            idx._walk_function(fn)
        for fn in idx.functions:
            fn.calls = [
                CallSite(cs.text, cs.line, cs.col, cs.held,
                         tuple(idx._resolve_call_text(cs.text, fn)))
                for cs in fn.calls]
        return idx

    def _index_module(self, mod) -> None:
        stem = mod.stem
        self.module_locks.setdefault(stem, {})

        def index_class(node: ast.ClassDef, outer: Optional[ClassInfo]):
            qname = (f"{outer.qname}.{node.name}" if outer
                     else node.name)
            ci = ClassInfo(
                name=node.name, qname=qname, relpath=mod.relpath,
                module_stem=stem,
                bases=[b for b in
                       (astutil.dotted(x) for x in node.bases) if b])
            self.classes.setdefault(node.name, []).append(ci)
            if outer is not None:
                outer.nested[node.name] = ci
            for child in node.body:
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    fi = FunctionInfo(
                        qname=f"{qname}.{child.name}", name=child.name,
                        relpath=mod.relpath, module_stem=stem,
                        node=child, cls=ci)
                    ci.methods[child.name] = fi
                    self.functions.append(fi)
                    self.methods_by_name.setdefault(
                        child.name, []).append(fi)
                elif isinstance(child, ast.ClassDef):
                    index_class(child, ci)

        for node in mod.tree.body:
            if isinstance(node, ast.ClassDef):
                index_class(node, None)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                fi = FunctionInfo(
                    qname=f"{stem}.{node.name}", name=node.name,
                    relpath=mod.relpath, module_stem=stem, node=node)
                self.functions.append(fi)
                self.module_funcs[(stem, node.name)] = fi
                self.methods_by_name.setdefault(
                    node.name, []).append(fi)
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _lock_ctor(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.module_locks[stem][tgt.id] = \
                            f"{stem}.{tgt.id}"

    def _index_class_attrs(self, mod) -> None:
        """Second pass: `self.x = ...` assignments in every method of
        every class — lock identities, attribute types, and which ctor
        params land verbatim in attributes."""
        for cls_list in self.classes.values():
            for ci in cls_list:
                if ci.relpath != mod.relpath:
                    continue
                for meth in ci.methods.values():
                    params = self._param_types(meth)
                    is_ctor = meth.name == "__init__"
                    for node in ast.walk(meth.node):
                        if not isinstance(node, (ast.Assign,
                                                 ast.AnnAssign)):
                            continue
                        targets = (node.targets
                                   if isinstance(node, ast.Assign)
                                   else [node.target])
                        ann = (node.annotation
                               if isinstance(node, ast.AnnAssign)
                               else None)
                        pairs: list[tuple] = []
                        for tgt in targets:
                            # `self.agg, self.shape = agg, shape`
                            if isinstance(tgt, (ast.Tuple, ast.List)) \
                                    and isinstance(node.value,
                                                   ast.Tuple) \
                                    and len(tgt.elts) == len(
                                        node.value.elts):
                                pairs.extend(zip(tgt.elts,
                                                 node.value.elts))
                            else:
                                pairs.append((tgt, node.value))
                        for tgt, value in pairs:
                            if not (isinstance(tgt, ast.Attribute)
                                    and isinstance(tgt.value, ast.Name)
                                    and tgt.value.id == "self"):
                                continue
                            self._record_self_attr(
                                ci, tgt.attr, value, ann, params,
                                is_ctor)

    def _record_self_attr(self, ci: ClassInfo, attr: str, value,
                          ann, params: dict[str, str],
                          is_ctor: bool) -> None:
        if isinstance(value, ast.Call) and _lock_ctor(value):
            ctor = (astutil.call_func_name(value) or "").rsplit(
                ".", 1)[-1]
            if ctor == "Condition" and value.args:
                # Condition(self._lock) guards the SAME underlying
                # lock: alias, don't mint a second identity
                inner = astutil.dotted(value.args[0])
                if inner and inner.startswith("self."):
                    wrapped = inner.split(".", 1)[1]
                    if wrapped in ci.attr_locks:
                        ci.attr_locks.setdefault(
                            attr, ci.attr_locks[wrapped])
                        return
            ci.attr_locks.setdefault(attr, f"{ci.name}.{attr}")
            return
        t = None
        if isinstance(value, ast.Call):
            callee = astutil.call_func_name(value) or ""
            simple = callee.rsplit(".", 1)[-1]
            if simple in self.classes:
                t = simple
        elif isinstance(value, ast.Name):
            t = params.get(value.id)
            if is_ctor:
                ci.ctor_param_attrs.setdefault(value.id, attr)
        if t is None and ann is not None:
            n = _ann_type_name(ann)
            if n in self.classes:
                t = n
        if t is not None:
            ci.attr_types.setdefault(attr, t)

    def _param_types(self, fn: FunctionInfo) -> dict[str, str]:
        out: dict[str, str] = {}
        args = fn.node.args
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            t = _ann_type_name(a.annotation)
            if t in self.classes:
                out[a.arg] = t
            elif a.arg in _PARAM_TYPE_HINTS \
                    and _PARAM_TYPE_HINTS[a.arg] in self.classes:
                out[a.arg] = _PARAM_TYPE_HINTS[a.arg]
        return out

    def _index_callback_bindings(self, mod) -> None:
        """`Destinations(handoff=self._reshard_handoff)` — when a
        constructor kwarg that the ctor assigns verbatim to an
        attribute is bound to a method reference, that method becomes a
        callee candidate for `self.<attr>(...)` inside the class."""
        for fn in self.functions:
            if fn.relpath != mod.relpath:
                continue
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                callee = astutil.call_func_name(node) or ""
                target = self._class_by_name(
                    callee.rsplit(".", 1)[-1], fn.module_stem)
                if target is None:
                    continue
                for kw in node.keywords:
                    if kw.arg is None:
                        continue
                    attr = target.ctor_param_attrs.get(kw.arg)
                    if attr is None:
                        continue
                    ref = astutil.dotted(kw.value)
                    bound = (self._resolve_method_ref(ref, fn)
                             if ref else None)
                    if bound is not None:
                        cands = target.attr_callables.setdefault(
                            attr, [])
                        if bound not in cands:
                            cands.append(bound)

    # -- symbol resolution -------------------------------------------------

    def _class_by_name(self, name: str,
                       prefer_stem: str) -> Optional[ClassInfo]:
        cands = self.classes.get(name)
        if not cands:
            return None
        if len(cands) == 1:
            return cands[0]
        same = [c for c in cands if c.module_stem == prefer_stem]
        return same[0] if len(same) == 1 else None

    def _mro_lookup(self, ci: ClassInfo, table: str, name: str,
                    _seen=None):
        _seen = _seen if _seen is not None else set()
        if ci.qname in _seen:
            return None
        _seen.add(ci.qname)
        got = getattr(ci, table).get(name)
        if got is not None:
            return got
        for base in ci.bases:
            bc = self._class_by_name(base.rsplit(".", 1)[-1],
                                     ci.module_stem)
            if bc is not None:
                got = self._mro_lookup(bc, table, name, _seen)
                if got is not None:
                    return got
        return None

    def resolve_method(self, ci: ClassInfo,
                       name: str) -> Optional[FunctionInfo]:
        return self._mro_lookup(ci, "methods", name)

    def _ctor_chain(self, ci: ClassInfo) -> list[FunctionInfo]:
        """Calling a class: its __init__ runs; a `with Ctor():` also
        enters __enter__/__exit__ (handled by the caller)."""
        init = self.resolve_method(ci, "__init__")
        return [init] if init is not None else []

    def _local_env(self, fn: FunctionInfo) -> dict[str, str]:
        """name -> project class name for locals with recoverable
        types; conflicting reassignments drop to untyped."""
        cached = self._env_memo.get(id(fn))
        if cached is not None:
            return cached
        env: dict[str, Optional[str]] = dict(self._param_types(fn))
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Assign):
                pairs = [(t, node.value) for t in node.targets]
            elif isinstance(node, ast.AnnAssign):
                pairs = [(node.target, node.value)]
            else:
                continue
            for tgt, value in pairs:
                if not isinstance(tgt, ast.Name):
                    continue
                t = self._expr_type(value, fn, env)
                if isinstance(node, ast.AnnAssign) and t is None:
                    t = _ann_type_name(node.annotation)
                    if t not in self.classes:
                        t = None
                prev = env.get(tgt.id, "\x00")
                if prev == "\x00":
                    env[tgt.id] = t
                elif prev != t:
                    env[tgt.id] = None
        out = {k: v for k, v in env.items() if v}
        self._env_memo[id(fn)] = out
        return out

    def _expr_type(self, value, fn: FunctionInfo,
                   env: dict) -> Optional[str]:
        if value is None:
            return None
        if isinstance(value, ast.Call):
            callee = astutil.call_func_name(value) or ""
            simple = callee.rsplit(".", 1)[-1]
            if simple in self.classes \
                    and self._class_by_name(simple,
                                            fn.module_stem) is not None:
                return simple
            target = self._resolve_method_ref(callee, fn, env)
            if target is not None:
                ret = getattr(target.node, "returns", None)
                t = _ann_type_name(ret)
                if t in self.classes:
                    return t
            return None
        text = astutil.dotted(value)
        if text is None:
            return None
        parts = text.split(".")
        if parts[0] == "self" and fn.cls is not None:
            if len(parts) == 2:
                return self._mro_lookup(fn.cls, "attr_types", parts[1])
            if len(parts) == 3:
                # `dest = self.proxy.destinations`
                t = self._mro_lookup(fn.cls, "attr_types", parts[1])
                tc = (self._class_by_name(t, fn.module_stem)
                      if t else None)
                if tc is not None:
                    return self._mro_lookup(tc, "attr_types", parts[2])
            return None
        if len(parts) == 1:
            return env.get(parts[0])
        return None

    def _resolve_method_ref(self, text: Optional[str], fn: FunctionInfo,
                            env: Optional[dict] = None
                            ) -> Optional[FunctionInfo]:
        """A *reference* to a function/method (no call): used for
        callback bindings and call resolution alike."""
        if not text:
            return None
        cands = self._resolve_call_text(text, fn, env)
        return cands[0] if len(cands) == 1 else None

    def _resolve_call_text(self, text: Optional[str], fn: FunctionInfo,
                           env: Optional[dict] = None
                           ) -> list[FunctionInfo]:
        if not text:
            self.unresolved_calls += 1
            return []
        parts = text.split(".")
        # self.m() / self.attr.m() / self.NestedClass()
        if parts[0] == "self" and fn.cls is not None:
            if len(parts) == 2:
                m = self.resolve_method(fn.cls, parts[1])
                if m is not None:
                    return [m]
                nested = self._mro_lookup(fn.cls, "nested", parts[1])
                if nested is not None:
                    return self._ctor_chain(nested)
                cbs = self._mro_lookup(fn.cls, "attr_callables",
                                       parts[1])
                if cbs:
                    return list(cbs)
            elif len(parts) == 3:
                t = self._mro_lookup(fn.cls, "attr_types", parts[1])
                tc = (self._class_by_name(t, fn.module_stem)
                      if t else None)
                if tc is not None:
                    m = self.resolve_method(tc, parts[2])
                    if m is not None:
                        return [m]
            return self._unique_fallback(parts[-1])
        if len(parts) == 1:
            name = parts[0]
            mf = self.module_funcs.get((fn.module_stem, name))
            if mf is not None:
                return [mf]
            ci = self._class_by_name(name, fn.module_stem)
            if ci is not None:
                return self._ctor_chain(ci)
            return []          # builtin / imported: out of scope
        if len(parts) == 2:
            base, name = parts
            # module-qualified: serving.fetch, failpoints.inject
            mf = self.module_funcs.get((base, name))
            if mf is not None:
                return [mf]
            bc = self.classes.get(name)
            if base in self.module_locks and bc:
                ci = self._class_by_name(name, base)
                if ci is not None:
                    return self._ctor_chain(ci)
            # ClassName.method (unbound)
            ci = self._class_by_name(base, fn.module_stem)
            if ci is not None:
                m = self.resolve_method(ci, name)
                if m is not None:
                    return [m]
                nested = ci.nested.get(name)
                if nested is not None:
                    return self._ctor_chain(nested)
            # typed local receiver
            env = env if env is not None else self._local_env(fn)
            t = env.get(base)
            tc = self._class_by_name(t, fn.module_stem) if t else None
            if tc is not None:
                m = self.resolve_method(tc, name)
                if m is not None:
                    return [m]
            return self._unique_fallback(name)
        return self._unique_fallback(parts[-1])

    def _unique_fallback(self, name: str) -> list[FunctionInfo]:
        if name in _GENERIC_METHOD_NAMES or name.startswith("__") \
                or len(name) <= 3:
            self.unresolved_calls += 1
            return []
        cands = self.methods_by_name.get(name, [])
        if len(cands) == 1:
            return [cands[0]]
        if cands and self._one_family(cands, name):
            return list(cands)
        self.unresolved_calls += 1
        return []

    def _one_family(self, cands: list, name: str) -> bool:
        """Every definition of `name` is a method of ONE class
        hierarchy, rooted at a class that defines it too: a call on an
        untyped receiver (`for ar in arenas: ar.sync()`) can only be
        dynamic dispatch inside that family, so it reaches every
        override (DigestArena.sync and SetArena.sync through
        _ArenaBase.sync)."""
        def root(ci: ClassInfo) -> ClassInfo:
            for base in ci.bases:
                bc = self._class_by_name(base.rsplit(".", 1)[-1],
                                         ci.module_stem)
                if bc is not None and self.resolve_method(
                        bc, name) is not None:
                    return root(bc)
            return ci
        roots = {root(c.cls).qname if c.cls is not None else None
                 for c in cands}
        return len(roots) == 1 and None not in roots

    # -- lock identity -----------------------------------------------------

    def resolve_lock_expr(self, node, fn: FunctionInfo,
                          env: dict) -> Optional[str]:
        """Canonical lock identity for a `with <expr>:` item or an
        explicit `<expr>.acquire()` receiver; None when the expression
        is neither a known lock nor lockish-looking."""
        from veneur_tpu.analysis.rules import lockguard
        text = astutil.dotted(node)
        if text is None:
            if isinstance(node, ast.Call):
                name = astutil.call_func_name(node)
                if lockguard._lockish(name):
                    return f"{fn.module_stem}.{name}()"
            return None
        parts = text.split(".")
        known: Optional[str] = None
        if parts[0] == "self" and fn.cls is not None:
            if len(parts) == 2:
                known = self._mro_lookup(fn.cls, "attr_locks", parts[1])
                if known is None and lockguard._lockish(text):
                    known = f"{fn.cls.name}.{parts[1]}"
            elif len(parts) == 3:
                t = self._mro_lookup(fn.cls, "attr_types", parts[1])
                tc = (self._class_by_name(t, fn.module_stem)
                      if t else None)
                if tc is not None:
                    known = self._mro_lookup(tc, "attr_locks", parts[2])
                if known is None and lockguard._lockish(text):
                    known = f"{t or '?'}.{parts[2]}"
        elif len(parts) == 1:
            known = self.module_locks.get(fn.module_stem,
                                          {}).get(parts[0])
            if known is None and lockguard._lockish(text):
                known = f"{fn.module_stem}.{parts[0]}"
        elif len(parts) == 2:
            known = self.module_locks.get(parts[0], {}).get(parts[1])
            if known is None:
                t = env.get(parts[0])
                tc = (self._class_by_name(t, fn.module_stem)
                      if t else None)
                if tc is not None:
                    known = self._mro_lookup(tc, "attr_locks", parts[1])
                if known is None and lockguard._lockish(text):
                    known = f"{t or fn.module_stem}.{parts[1]}"
        elif lockguard._lockish(text):
            known = f"?{fn.module_stem}:{text}"
        return known

    # -- per-function walk -------------------------------------------------

    def _scan_explicit_acquires(self, fn: FunctionInfo) -> None:
        """Lexically unmatched `X.acquire()` / `X.release()` on known
        locks: `reshard_begin` returns holding `_reshard_serial`,
        `reshard_commit` releases a lock it never acquired."""
        env = self._local_env(fn)
        counts: dict[str, int] = {}
        for node in ast.walk(fn.node):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("acquire", "release")):
                continue
            lock = self.resolve_lock_expr(node.func.value, fn, env)
            if lock is None:
                continue
            delta = 1 if node.func.attr == "acquire" else -1
            counts[lock] = counts.get(lock, 0) + delta
        fn.leaves_held = tuple(sorted(
            k for k, v in counts.items() if v > 0))
        fn.releases = tuple(sorted(
            k for k, v in counts.items() if v < 0))

    def _walk_function(self, fn: FunctionInfo) -> None:
        from veneur_tpu.analysis.rules import lockguard
        env = self._local_env(fn)
        host_lists = lockguard._host_list_names(fn.node)
        held: list[tuple[str, int]] = []
        if fn.name.endswith("_locked"):
            held.append((CONVENTION_PREFIX + fn.qname,
                         fn.node.lineno))

        def handle_call(call: ast.Call) -> None:
            text = astutil.dotted(call.func)
            label = lockguard._describe_call(call, host_lists)
            if label is not None:
                fn.blocking_direct.append((label, call.lineno))
            if text is None:
                if isinstance(call.func, ast.Attribute):
                    self.unresolved_calls += 1
                return
            fn.calls.append(CallSite(text, call.lineno,
                                     call.col_offset, tuple(held)))
            # explicit acquire/release sequencing within this body
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr in ("acquire", "release"):
                lock = self.resolve_lock_expr(call.func.value, fn, env)
                if lock is not None:
                    if call.func.attr == "acquire":
                        fn.acquisitions.append(Acquisition(
                            lock, call.lineno, tuple(held)))
                        held.append((lock, call.lineno))
                    else:
                        for i in range(len(held) - 1, -1, -1):
                            if held[i][0] == lock:
                                del held[i]
                                break
                return
            # a call into a function that RETURNS holding a lock (or
            # that releases one) extends/shrinks the held set for the
            # remainder of this body — the cross-function
            # begin()/commit() window
            cands = self._resolve_call_text(text, fn, env)
            if len(cands) == 1:
                for lock in cands[0].leaves_held:
                    fn.acquisitions.append(Acquisition(
                        lock, call.lineno, tuple(held)))
                    held.append((lock, call.lineno))
                for lock in cands[0].releases:
                    for i in range(len(held) - 1, -1, -1):
                        if held[i][0] == lock:
                            del held[i]
                            break

        def visit(node) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                return      # deferred execution / new scope
            if isinstance(node, (ast.With, ast.AsyncWith)):
                pushed_entries: list[tuple[str, int]] = []
                for item in node.items:
                    if isinstance(item.context_expr, ast.Call):
                        visit(item.context_expr)
                        # `with Ctor():` also runs __enter__/__exit__
                        text = astutil.dotted(item.context_expr.func)
                        cands = self._resolve_call_text(text, fn, env) \
                            if text else []
                        if len(cands) == 1 \
                                and cands[0].name == "__init__" \
                                and cands[0].cls is not None:
                            for hook in ("__enter__", "__exit__"):
                                m = self.resolve_method(cands[0].cls,
                                                        hook)
                                if m is not None:
                                    fn.calls.append(CallSite(
                                        f"{cands[0].cls.name}.{hook}",
                                        item.context_expr.lineno,
                                        item.context_expr.col_offset,
                                        tuple(held)))
                    lock = self.resolve_lock_expr(item.context_expr,
                                                  fn, env)
                    if lock is not None:
                        fn.acquisitions.append(Acquisition(
                            lock, item.context_expr.lineno,
                            tuple(held)))
                        entry = (lock, item.context_expr.lineno)
                        held.append(entry)
                        pushed_entries.append(entry)
                for stmt in node.body:
                    visit(stmt)
                # remove exactly the entries THIS with pushed (by
                # identity): a bare `.acquire()` or a begin()-style
                # window opened inside the body appends entries that
                # must survive the with-block's exit — popping the
                # tail would release the wrong lock
                for entry in pushed_entries:
                    for i in range(len(held) - 1, -1, -1):
                        if held[i] is entry:
                            del held[i]
                            break
                return
            if isinstance(node, ast.Call):
                handle_call(node)
            for child in ast.iter_child_nodes(node):
                visit(child)

        for stmt in fn.node.body:
            visit(stmt)

    # -- derived analyses --------------------------------------------------

    def reach_acquisitions(self, fn: FunctionInfo, _depth: int = 0,
                           _stack: Optional[set] = None) -> dict:
        """lock -> (call chain of qnames from `fn`, (relpath, line) of
        the acquisition): every lock acquired by `fn` or anything it
        can reach.  Shortest-first; memoized; cycle-safe."""
        memo = self._reach_memo.get(id(fn))
        if memo is not None:
            return memo
        _stack = _stack if _stack is not None else set()
        if id(fn) in _stack or _depth > _MAX_CHAIN_DEPTH:
            self._truncations += 1
            return {}
        _stack.add(id(fn))
        t0 = self._truncations
        out: dict[str, tuple] = {}
        for acq in fn.acquisitions:
            out.setdefault(acq.lock, ((), (fn.relpath, acq.line)))
        for cs in fn.calls:
            for callee in cs.callees:
                sub = self.reach_acquisitions(callee, _depth + 1,
                                              _stack)
                for lock, (chain, site) in sorted(sub.items()):
                    out.setdefault(
                        lock, ((callee.qname,) + chain, site))
        _stack.discard(id(fn))
        if self._truncations == t0:
            # complete traversal only: a cycle-/depth-truncated result
            # cached here would be replayed for callers that could
            # have seen the full reach
            self._reach_memo[id(fn)] = out
        return out

    def blocking_chain(self, fn: FunctionInfo, _depth: int = 0,
                       _stack: Optional[set] = None) -> Optional[tuple]:
        """(chain of qnames, blocking-op label, (relpath, line)) when
        `fn` reaches a blocking operation through any call chain; None
        otherwise."""
        if id(fn) in self._block_memo:
            return self._block_memo[id(fn)]
        _stack = _stack if _stack is not None else set()
        if id(fn) in _stack or _depth > _MAX_CHAIN_DEPTH:
            self._truncations += 1
            return None
        _stack.add(id(fn))
        t0 = self._truncations
        result: Optional[tuple] = None
        if fn.blocking_direct:
            label, line = fn.blocking_direct[0]
            result = ((), label, (fn.relpath, line))
        else:
            best: Optional[tuple] = None
            for cs in fn.calls:
                for callee in cs.callees:
                    sub = self.blocking_chain(callee, _depth + 1,
                                              _stack)
                    if sub is None:
                        continue
                    chain = (callee.qname,) + sub[0]
                    if best is None or len(chain) < len(best[0]):
                        best = (chain, sub[1], sub[2])
            result = best
        _stack.discard(id(fn))
        if self._truncations == t0:
            self._block_memo[id(fn)] = result
        return result

    # -- the lock-order graph ----------------------------------------------

    def lock_order_edges(self) -> dict:
        """(src, dst) -> list of witness dicts.  An edge means: `dst`
        is acquired somewhere while `src` is held — lexically nested,
        or through a call chain from inside `src`'s region."""
        edges: dict[tuple[str, str], list[dict]] = {}

        def add(src: str, dst: str, holder: FunctionInfo, line: int,
                chain: tuple, site: tuple) -> None:
            if src.startswith(CONVENTION_PREFIX):
                return
            wits = edges.setdefault((src, dst), [])
            if len(wits) < 3:
                w = {"holder": holder.qname,
                     "holder_site": f"{holder.relpath}:{line}",
                     "chain": list(chain),
                     "acquire_site": f"{site[0]}:{site[1]}"}
                if w not in wits:
                    wits.append(w)

        for fn in sorted(self.functions, key=lambda f: f.qname):
            for acq in fn.acquisitions:
                for src, line in acq.held:
                    add(src, acq.lock, fn, line, (),
                        (fn.relpath, acq.line))
            for cs in fn.calls:
                if not cs.held:
                    continue
                for callee in cs.callees:
                    for lock, (chain, site) in sorted(
                            self.reach_acquisitions(callee).items()):
                        for src, _line in cs.held:
                            add(src, lock, fn, cs.line,
                                (callee.qname,) + chain, site)
        return edges

    @staticmethod
    def find_cycles(edges: dict) -> list[list[str]]:
        """Cycles in the lock-order graph (potential deadlocks): one
        representative cycle per SCC with >1 node, plus self-loops.
        Deterministic output order."""
        adj: dict[str, set[str]] = {}
        for (a, b) in edges:
            adj.setdefault(a, set()).add(b)
            adj.setdefault(b, set())
        # Tarjan SCC, iterative for safety
        index: dict[str, int] = {}
        low: dict[str, int] = {}
        on: set[str] = set()
        stack: list[str] = []
        sccs: list[list[str]] = []
        counter = [0]

        def strongconnect(v0: str) -> None:
            work = [(v0, iter(sorted(adj[v0])))]
            index[v0] = low[v0] = counter[0]
            counter[0] += 1
            stack.append(v0)
            on.add(v0)
            while work:
                v, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index:
                        index[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on.add(w)
                        work.append((w, iter(sorted(adj[w]))))
                        advanced = True
                        break
                    if w in on:
                        low[v] = min(low[v], index[w])
                if advanced:
                    continue
                work.pop()
                if work:
                    pv = work[-1][0]
                    low[pv] = min(low[pv], low[v])
                if low[v] == index[v]:
                    scc = []
                    while True:
                        w = stack.pop()
                        on.discard(w)
                        scc.append(w)
                        if w == v:
                            break
                    sccs.append(sorted(scc))

        for v in sorted(adj):
            if v not in index:
                strongconnect(v)

        cycles: list[list[str]] = []
        for scc in sccs:
            if len(scc) > 1:
                cycles.append(scc)
            elif (scc[0], scc[0]) in edges:
                cycles.append(scc)
        return sorted(cycles)

    def to_graph_dict(self, root: str = "") -> dict:
        """The exportable lock-order graph: nodes, edges with witness
        chains, cycles — the committed artifact and the witness
        comparator's static side."""
        edges = self.lock_order_edges()
        cycles = self.find_cycles(edges)
        locks = sorted({x for e in edges for x in e}
                       | {acq.lock for fn in self.functions
                          for acq in fn.acquisitions
                          if not acq.lock.startswith(
                              CONVENTION_PREFIX)})
        return {
            "vnlint_lock_graph": 1,
            "root": root,
            "locks": locks,
            "edges": [
                {"src": a, "dst": b, "witnesses": wits}
                for (a, b), wits in sorted(edges.items())],
            "cycles": [
                {"locks": c,
                 "edges": [[a, b] for (a, b) in sorted(edges)
                           if a in c and b in c]}
                for c in cycles],
            "functions": len(self.functions),
            "unresolved_calls": self.unresolved_calls,
        }


def index_for(ctx) -> ConcurrencyIndex:
    """The per-run shared index, cached on the ProjectContext so the
    lock-order and blocking-propagation rules build it once."""
    idx = getattr(ctx, "_concurrency_index", None)
    if idx is None:
        idx = ConcurrencyIndex.build(ctx.modules)
        ctx._concurrency_index = idx
    return idx


def build_index(paths=None):
    """Standalone build over `paths` (default: the veneur_tpu package)
    — the witness comparator's entry point; returns (ProjectContext,
    ConcurrencyIndex).  Discovery/parsing is the engine's own
    (engine.load_modules), so the graph always covers exactly the tree
    the lint run sees."""
    from veneur_tpu.analysis import engine as engine_mod
    eng = engine_mod.LintEngine(rules=[])
    _root, modules, _failures = engine_mod.load_modules(
        paths, eng.known_rules)
    ctx = engine_mod.ProjectContext(modules)
    return ctx, index_for(ctx)
