"""Project-wide symbol table + call graph for interprocedural rules.

The per-module :class:`~dstack_tpu.analysis.core.Module` passes (DT1xx-DT5xx)
deliberately stop at file boundaries; the SPMD invariants (DT6xx) cannot —
an ``axis_name`` is chosen in ``models/llama.py``, threaded through a
``functools.partial`` in ``ops/ring_attention.py``, and finally consumed by
``lax.ppermute`` three call frames down, and "this collective runs inside
``shard_map``" is a property of the *call graph*, not of any one module.

:class:`Project` indexes every scanned module once and answers three
questions for the rules:

- **constant resolution** (:meth:`Project.resolve_strs`): the set of string
  values an expression can take, looking through module constants
  (``mesh.SEQ``), tuple unpacking, dataclass field defaults
  (``policy.tensor_axis`` via the ``ShardingPolicy`` class body), default
  parameter values, and — interprocedurally — every call site that binds the
  parameter, including ``functools.partial(fn, axis_name=...)`` bindings;
- **axis names** (:meth:`Project.axis_names`): the canonical mesh axis set,
  read from the scanned tree's ``AXIS_ORDER`` tuple (``parallel/mesh.py``)
  rather than hard-coded, with a documented fallback for partial scans;
- **shard_map reachability** (:meth:`Project.is_shard_mapped`): the
  transitive closure of "wrapped by ``shard_map``/``pmap``" over function
  references — a function referenced (called, or passed to ``lax.scan``/
  ``fori_loop``/``checkpoint``) from inside a shard-mapped function runs
  under manual SPMD too.

Resolution is *may* analysis: it returns every string that can plausibly
flow to the expression and the empty set when nothing resolves, which
rules treat as "unknown — stay silent".  Shard_map REACHABILITY is the
one property that needs the whole tree in view (a wrapper outside the
scanned set is indistinguishable from no wrapper), so the pre-commit
hook and CI both run the full-tree scan rather than changed files.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from dstack_tpu.analysis.core import Module, qualified_name

__all__ = [
    "Project", "Scope", "FuncInfo",
    "DEFAULT_AXIS_NAMES", "TRACER_NAMES", "PARTIAL_NAMES",
    "COMPUTE_SCOPE_PREFIXES",
]

#: The compute plane — where the SPMD invariants (DT6xx) apply.  One
#: definition shared by both rule modules so they can never disagree on
#: which modules they cover.
COMPUTE_SCOPE_PREFIXES = (
    "dstack_tpu/models/",
    "dstack_tpu/ops/",
    "dstack_tpu/parallel/",
    "dstack_tpu/serving/",
)

#: Fallback canonical mesh axes, used only when no scanned module defines an
#: ``AXIS_ORDER`` tuple (e.g. a file-scoped pre-commit run that did not
#: include ``parallel/mesh.py``).  Must mirror ``parallel/mesh.py``.
DEFAULT_AXIS_NAMES: FrozenSet[str] = frozenset(
    ("dcn", "stage", "data", "fsdp", "expert", "seq", "tensor")
)

#: manual-SPMD entry points: functions wrapped by these run with mesh axes
#: bound (collectives inside are legal)
TRACER_NAMES = frozenset({
    "shard_map", "jax.shard_map",
    "jax.experimental.shard_map.shard_map", "jax.experimental.shard_map",
    "pmap", "jax.pmap",
})

PARTIAL_NAMES = frozenset({"partial", "functools.partial"})

_MAX_DEPTH = 8  # call-site propagation depth cap (cycles are also guarded)


class FuncInfo:
    """One function definition: node + owning module + dotted names."""

    __slots__ = ("node", "module", "qualname", "full")

    def __init__(self, node: ast.AST, module: Module, qualname: str,
                 full: str) -> None:
        self.node = node
        self.module = module
        self.qualname = qualname
        self.full = full

    def positional_params(self) -> List[ast.arg]:
        a = self.node.args
        params = list(a.posonlyargs) + list(a.args)
        if params and params[0].arg in ("self", "cls"):
            params = params[1:]
        return params

    def all_params(self) -> List[ast.arg]:
        a = self.node.args
        return self.positional_params() + list(a.kwonlyargs)

    def param_default(self, name: str) -> Optional[ast.expr]:
        a = self.node.args
        pos = list(a.posonlyargs) + list(a.args)
        defaults = list(a.defaults)
        # defaults align to the TAIL of the positional list
        for p, d in zip(pos[len(pos) - len(defaults):], defaults):
            if p.arg == name:
                return d
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg == name and d is not None:
                return d
        return None


class Scope:
    """Resolution context: a module plus the innermost-first chain of
    enclosing function defs (closure lookups walk the chain outward)."""

    __slots__ = ("module", "chain")

    def __init__(self, module: Module, chain: Tuple[ast.AST, ...]) -> None:
        self.module = module
        self.chain = chain


def _module_name(relpath: str) -> str:
    name = relpath[:-3] if relpath.endswith(".py") else relpath
    parts = [p for p in name.split("/") if p]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _const_value(expr: ast.expr):
    """Constant string, or tuple of constant strings, else None."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, (ast.Tuple, ast.List)):
        vals = []
        for e in expr.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


class Project:
    """Cross-module index over every scanned :class:`Module`."""

    def __init__(self, modules: Iterable[Module]) -> None:
        self.modules: List[Module] = list(modules)
        self.by_relpath: Dict[str, Module] = {
            m.relpath: m for m in self.modules
        }
        self._mod_names: Dict[int, str] = {
            id(m): _module_name(m.relpath) for m in self.modules
        }
        #: "pkg.mod.NAME" -> str value (module-level string constants)
        self.str_consts: Dict[str, str] = {}
        #: "pkg.mod.NAME" -> tuple of strings (AXIS_ORDER and friends)
        self.tuple_consts: Dict[str, Tuple[str, ...]] = {}
        #: "pkg.mod.Cls.field" -> str | tuple (class-body field defaults —
        #: how ``policy.tensor_axis`` resolves through ShardingPolicy)
        self.class_fields: Dict[str, object] = {}
        #: class full name -> module; plus short-name index
        self.classes: Dict[str, Module] = {}
        self._class_short: Dict[str, List[str]] = {}
        #: function full name -> FuncInfo
        self.functions: Dict[str, FuncInfo] = {}
        self._func_of_node: Dict[int, FuncInfo] = {}
        #: callee full name -> [(call node, Scope, is_partial)]
        self._call_sites: Dict[str, List[Tuple[ast.Call, Scope, bool]]] = {}
        self._resolving: Set[Tuple[str, str]] = set()  # (func full, param)
        self._memo: Dict[Tuple[str, str], FrozenSet[str]] = {}
        #: id(enclosing fn or None) -> {name: FuncInfo} (direct nested defs)
        self._children: Dict[Optional[int], Dict[str, FuncInfo]] = {}
        #: id(fn) -> {name: [value exprs]} (single-target + tuple-unpack
        #: assignments, precomputed so Name resolution is O(depth))
        self._assigns: Dict[int, Dict[str, List[ast.expr]]] = {}
        self._axis_names: Optional[FrozenSet[str]] = None
        self._shard_mapped: Optional[Set[int]] = None
        self._returns_donate: Dict[str, Optional[Tuple[Tuple[int, ...],
                                                       Tuple[str, ...]]]] = {}
        for m in self.modules:
            self._index_module(m)
        for m in self.modules:
            self._index_calls(m)

    # -- indexing ----------------------------------------------------------

    def mod_name(self, module: Module) -> str:
        return self._mod_names[id(module)]

    def _index_module(self, m: Module) -> None:
        modname = self.mod_name(m)
        for node in m.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = m.qualname.get(node, node.name)
                info = FuncInfo(node, m, qual, f"{modname}.{qual}")
                self.functions.setdefault(info.full, info)
                self._func_of_node[id(node)] = info
                parent = m.func_of.get(node)
                key = id(parent) if parent is not None else None
                self._children.setdefault(key, {}).setdefault(
                    node.name, info)
            elif isinstance(node, ast.Assign):
                fn = m.func_of.get(node)
                if fn is None:
                    continue
                per = self._assigns.setdefault(id(fn), {})
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        per.setdefault(t.id, []).append(node.value)
                    elif isinstance(t, ast.Tuple) and isinstance(
                            node.value, ast.Tuple) and len(t.elts) == len(
                            node.value.elts):
                        for te, ve in zip(t.elts, node.value.elts):
                            if isinstance(te, ast.Name):
                                per.setdefault(te.id, []).append(ve)
            elif isinstance(node, ast.ClassDef):
                # class qualname: rebuild from parents via qualname of a
                # child function, else module-level name
                full = self._class_full(m, node, modname)
                self.classes[full] = m
                self._class_short.setdefault(node.name, []).append(full)
                for stmt in node.body:
                    target = value = None
                    if isinstance(stmt, ast.AnnAssign) and isinstance(
                            stmt.target, ast.Name) and stmt.value is not None:
                        target, value = stmt.target.id, stmt.value
                    elif isinstance(stmt, ast.Assign) and len(
                            stmt.targets) == 1 and isinstance(
                            stmt.targets[0], ast.Name):
                        target, value = stmt.targets[0].id, stmt.value
                    if target is None:
                        continue
                    v = _const_value(value)
                    if v is not None:
                        self.class_fields[f"{full}.{target}"] = v
        for stmt in m.tree.body:
            target = value = None
            if isinstance(stmt, ast.Assign) and len(
                    stmt.targets) == 1 and isinstance(
                    stmt.targets[0], ast.Name):
                target, value = stmt.targets[0].id, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name) and stmt.value is not None:
                target, value = stmt.target.id, stmt.value
            if target is None:
                continue
            v = _const_value(value)
            if isinstance(v, str):
                self.str_consts[f"{modname}.{target}"] = v
            elif isinstance(v, tuple):
                self.tuple_consts[f"{modname}.{target}"] = v
            elif isinstance(value, (ast.Tuple, ast.List)):
                # tuple of Names referencing module string constants
                # (AXIS_ORDER = (DCN, STAGE, ...)) — resolve one level
                vals = []
                for e in value.elts:
                    if isinstance(e, ast.Name):
                        s = self.str_consts.get(f"{modname}.{e.id}")
                        if s is None:
                            break
                        vals.append(s)
                    elif isinstance(e, ast.Constant) and isinstance(
                            e.value, str):
                        vals.append(e.value)
                    else:
                        break
                else:
                    if vals:
                        self.tuple_consts[f"{modname}.{target}"] = \
                            tuple(vals)

    def _class_full(self, m: Module, node: ast.ClassDef,
                    modname: str) -> str:
        parts = [node.name]
        cur = m.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.ClassDef, ast.FunctionDef,
                                ast.AsyncFunctionDef)):
                parts.append(cur.name)
            cur = m.parents.get(cur)
        return f"{modname}." + ".".join(reversed(parts))

    def _index_calls(self, m: Module) -> None:
        for node in m.nodes:
            if not isinstance(node, ast.Call):
                continue
            scope = self.scope_at(m, node)
            name = qualified_name(node.func, m.aliases)
            if name in PARTIAL_NAMES and node.args:
                target = self.resolve_func(node.args[0], scope)
                if target is not None:
                    self._call_sites.setdefault(target.full, []).append(
                        (node, scope, True))
                continue
            target = self.resolve_func(node.func, scope)
            if target is not None:
                self._call_sites.setdefault(target.full, []).append(
                    (node, scope, False))

    # -- lookups -----------------------------------------------------------

    def scope_at(self, m: Module, node: ast.AST) -> Scope:
        chain: List[ast.AST] = []
        fn = m.func_of.get(node)
        while fn is not None:
            chain.append(fn)
            fn = m.func_of.get(fn)
        return Scope(m, tuple(chain))

    def func_info(self, node: ast.AST) -> Optional[FuncInfo]:
        return self._func_of_node.get(id(node))

    def local_assignments(self, fn_node: ast.AST) -> Dict[str,
                                                          List[ast.expr]]:
        """name -> assigned value exprs inside ``fn_node`` (single-target
        and tuple-unpack assignments, as indexed for Name resolution)."""
        return self._assigns.get(id(fn_node), {})

    def call_sites(self, full: str) -> List[Tuple[ast.Call, Scope, bool]]:
        """Indexed call sites of the function named ``full``:
        (call node, scope, is_partial) triples.  Only calls whose callee
        expression resolved (direct names / module-qualified attributes)
        appear — attribute calls on unknown receivers do not."""
        return self._call_sites.get(full, [])

    def resolve_func(self, expr: ast.expr,
                     scope: Scope) -> Optional[FuncInfo]:
        """Function definition an expression refers to: nested defs in the
        enclosing scope chain first, then module level, then imports."""
        m = scope.module
        if isinstance(expr, ast.Name):
            for fn in scope.chain:
                hit = self._children.get(id(fn), {}).get(expr.id)
                if hit is not None and hit.module is m:
                    return hit
            modname = self.mod_name(m)
            info = self.functions.get(f"{modname}.{expr.id}")
            if info is not None:
                return info
            full = m.aliases.get(expr.id)
            if full is not None:
                return self.functions.get(full)
            return None
        if isinstance(expr, ast.Attribute):
            full = qualified_name(expr, m.aliases)
            if full is not None:
                return self.functions.get(full)
        return None

    def axis_names(self) -> FrozenSet[str]:
        """Union of every ``AXIS_ORDER`` tuple in the scanned tree, falling
        back to :data:`DEFAULT_AXIS_NAMES` when none is in scope."""
        if self._axis_names is None:
            found: Set[str] = set()
            for key, vals in self.tuple_consts.items():
                if key.rsplit(".", 1)[-1] == "AXIS_ORDER":
                    found.update(vals)
            self._axis_names = frozenset(found) if found \
                else DEFAULT_AXIS_NAMES
        return self._axis_names

    # -- string resolution -------------------------------------------------

    def resolve_strs(self, expr: Optional[ast.expr], scope: Scope,
                     depth: int = 0) -> FrozenSet[str]:
        """Every string constant that can flow to ``expr`` (may analysis;
        tuples flatten; non-strings like ``None`` contribute nothing)."""
        if expr is None or depth > _MAX_DEPTH:
            return frozenset()
        if isinstance(expr, ast.Constant):
            return frozenset((expr.value,)) if isinstance(
                expr.value, str) else frozenset()
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            out: Set[str] = set()
            for e in expr.elts:
                out.update(self.resolve_strs(e, scope, depth + 1))
            return frozenset(out)
        if isinstance(expr, ast.Starred):
            return self.resolve_strs(expr.value, scope, depth + 1)
        if isinstance(expr, ast.IfExp):
            return (self.resolve_strs(expr.body, scope, depth + 1)
                    | self.resolve_strs(expr.orelse, scope, depth + 1))
        if isinstance(expr, ast.BoolOp):
            out = set()
            for v in expr.values:
                out.update(self.resolve_strs(v, scope, depth + 1))
            return frozenset(out)
        if isinstance(expr, ast.Name):
            return self._resolve_name(expr.id, scope, depth)
        if isinstance(expr, ast.Attribute):
            return self._resolve_attribute(expr, scope, depth)
        return frozenset()

    def _resolve_name(self, name: str, scope: Scope,
                      depth: int) -> FrozenSet[str]:
        m = scope.module
        for i, fn in enumerate(scope.chain):
            inner = Scope(m, scope.chain[i:])
            values = self._assigns.get(id(fn), {}).get(name)
            if values:
                out: Set[str] = set()
                for v in values:
                    out.update(self.resolve_strs(v, inner, depth + 1))
                return frozenset(out)
            info = self._func_of_node.get(id(fn))
            if info is not None and any(
                    p.arg == name for p in info.all_params()):
                return self._resolve_param(info, name, depth)
            # a bare (unindexed) lambda or comprehension scope: fall through
        modname = self.mod_name(m)
        qual = f"{modname}.{name}"
        if qual in self.str_consts:
            return frozenset((self.str_consts[qual],))
        if qual in self.tuple_consts:
            return frozenset(self.tuple_consts[qual])
        full = m.aliases.get(name)
        if full is not None:
            if full in self.str_consts:
                return frozenset((self.str_consts[full],))
            if full in self.tuple_consts:
                return frozenset(self.tuple_consts[full])
        return frozenset()

    def _resolve_param(self, info: FuncInfo, param: str,
                       depth: int) -> FrozenSet[str]:
        key = (info.full, param)
        if key in self._memo:
            return self._memo[key]
        if key in self._resolving:
            return frozenset()  # recursion through the call graph
        self._resolving.add(key)
        try:
            out: Set[str] = set()
            default = info.param_default(param)
            if default is not None:
                out.update(self.resolve_strs(
                    default, Scope(info.module, ()), depth + 1))
            pos_names = [p.arg for p in info.positional_params()]
            for call, site_scope, is_partial in self._call_sites.get(
                    info.full, ()):
                bound: Optional[ast.expr] = None
                for kw in call.keywords:
                    if kw.arg == param:
                        bound = kw.value
                args = call.args[1:] if is_partial else call.args
                if bound is None and param in pos_names:
                    idx = pos_names.index(param)
                    if idx < len(args) and not any(
                            isinstance(a, ast.Starred) for a in args[:idx + 1]):
                        bound = args[idx]
                if bound is not None:
                    out.update(self.resolve_strs(
                        bound, site_scope, depth + 1))
            result = frozenset(out)
            self._memo[key] = result
            return result
        finally:
            self._resolving.discard(key)

    def _resolve_attribute(self, expr: ast.Attribute, scope: Scope,
                           depth: int) -> FrozenSet[str]:
        m = scope.module
        full = qualified_name(expr, m.aliases)
        if full is not None:
            if full in self.str_consts:
                return frozenset((self.str_consts[full],))
            if full in self.tuple_consts:
                return frozenset(self.tuple_consts[full])
        # instance-field default: ``policy.tensor_axis`` where ``policy``
        # types as a project class whose body declares the field default
        if isinstance(expr.value, ast.Name):
            for cls_full in self._classes_of(expr.value.id, scope):
                v = self.class_fields.get(f"{cls_full}.{expr.attr}")
                if isinstance(v, str):
                    return frozenset((v,))
                if isinstance(v, tuple):
                    return frozenset(v)
        return frozenset()

    def _classes_of(self, name: str, scope: Scope) -> List[str]:
        """Project classes the variable/parameter ``name`` may be an
        instance of, from annotations (``policy: ShardingPolicy``) or
        constructor defaults/assignments (``policy=ShardingPolicy()``)."""
        m = scope.module
        exprs: List[ast.expr] = []
        for fn in scope.chain:
            info = self._func_of_node.get(id(fn))
            if info is None:
                continue
            for p in info.all_params():
                if p.arg == name:
                    if p.annotation is not None:
                        exprs.append(p.annotation)
                    d = info.param_default(name)
                    if isinstance(d, ast.Call):
                        exprs.append(d.func)
            for v in self._assigns.get(id(fn), {}).get(name, ()):
                if isinstance(v, ast.Call):
                    exprs.append(v.func)
        out: List[str] = []
        for e in exprs:
            for node in ast.walk(e):
                if isinstance(node, (ast.Name, ast.Attribute)):
                    q = qualified_name(node, m.aliases)
                    cands = []
                    if q is not None:
                        if q in self.classes:
                            cands.append(q)
                        modq = f"{self.mod_name(m)}.{q}"
                        if modq in self.classes:
                            cands.append(modq)
                    if isinstance(node, ast.Name):
                        cands.extend(
                            c for c in self._class_short.get(node.id, ()))
                    for c in cands:
                        if c not in out:
                            out.append(c)
        return out

    # -- shard_map reachability --------------------------------------------

    def _tracer_target(self, expr: ast.expr, m: Module) -> Optional[str]:
        """Resolve a callee/decorator expr to a tracer entry point name,
        looking through ``partial(shard_map, ...)``."""
        if isinstance(expr, ast.Call):
            name = qualified_name(expr.func, m.aliases)
            if name in PARTIAL_NAMES and expr.args:
                return self._tracer_target(expr.args[0], m)
            return name if name in TRACER_NAMES else None
        name = qualified_name(expr, m.aliases)
        return name if name in TRACER_NAMES else None

    def shard_map_wrapped(self, call: ast.Call,
                          scope: Scope) -> Optional[FuncInfo]:
        """FuncInfo wrapped by a ``shard_map(...)`` call (through partial)."""
        target: Optional[ast.expr] = None
        if call.args:
            target = call.args[0]
        else:
            for kw in call.keywords:
                if kw.arg == "f":
                    target = kw.value
        if target is None:
            return None
        if isinstance(target, ast.Call):
            name = qualified_name(target.func, scope.module.aliases)
            if name in PARTIAL_NAMES and target.args:
                return self.resolve_func(target.args[0], scope)
            return None
        return self.resolve_func(target, scope)

    def _shard_map_seeds(self) -> Set[int]:
        seeds: Set[int] = set()
        for m in self.modules:
            for node in m.nodes:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for deco in node.decorator_list:
                        if self._tracer_target(deco, m):
                            seeds.add(id(node))
                elif isinstance(node, ast.Call):
                    if self._tracer_target(node.func, m) is None:
                        continue
                    info = self.shard_map_wrapped(node, self.scope_at(m, node))
                    if info is not None:
                        seeds.add(id(info.node))
        return seeds

    def is_shard_mapped(self, fn_node: ast.AST) -> bool:
        """Whether ``fn_node`` runs under manual SPMD: wrapped by
        shard_map/pmap, or referenced (transitively) from a function that
        is — references include higher-order uses like ``lax.scan(tick,
        ...)``, which is how the pipeline body's ``tick`` runs."""
        if self._shard_mapped is None:
            marked = self._shard_map_seeds()
            work = [self._func_of_node[i] for i in marked
                    if i in self._func_of_node]
            while work:
                info = work.pop()
                for sub in ast.walk(info.node):
                    if not isinstance(sub, (ast.Name, ast.Attribute)):
                        continue
                    if isinstance(sub, ast.Name) and not isinstance(
                            sub.ctx, ast.Load):
                        continue
                    ref = self.resolve_func(
                        sub, self.scope_at(info.module, sub))
                    if ref is not None and id(ref.node) not in marked:
                        marked.add(id(ref.node))
                        work.append(ref)
            self._shard_mapped = marked
        return id(fn_node) in self._shard_mapped

    # -- donation ----------------------------------------------------------

    def donate_spec(self, call: ast.Call,
                    m: Module) -> Optional[Tuple[Tuple[int, ...],
                                                 Tuple[str, ...]]]:
        """(argnums, argnames) when ``call`` is ``jax.jit(...)`` with
        donation, else None."""
        name = qualified_name(call.func, m.aliases)
        if name not in ("jax.jit", "jit", "pjit", "jax.pjit"):
            return None
        nums: Tuple[int, ...] = ()
        names: Tuple[str, ...] = ()
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                if isinstance(kw.value, ast.Constant) and isinstance(
                        kw.value.value, int):
                    nums = (kw.value.value,)
                elif isinstance(kw.value, (ast.Tuple, ast.List)):
                    nums = tuple(
                        e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int))
            elif kw.arg == "donate_argnames":
                if isinstance(kw.value, ast.Constant) and isinstance(
                        kw.value.value, str):
                    names = (kw.value.value,)
                elif isinstance(kw.value, (ast.Tuple, ast.List)):
                    names = tuple(
                        e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str))
        if nums or names:
            return nums, names
        return None

    def returns_donating(
            self, info: FuncInfo) -> Optional[Tuple[Tuple[int, ...],
                                                    Tuple[str, ...]]]:
        """Donation spec when ``info`` returns a jit-with-donation callable
        (the ``make_train_step`` factory shape): a return of ``jax.jit(...,
        donate_argnums=...)`` directly or of a local bound to one."""
        if info.full in self._returns_donate:
            return self._returns_donate[info.full]
        self._returns_donate[info.full] = None  # cycle guard
        m = info.module
        jit_locals: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {}
        for sub in ast.walk(info.node):
            if isinstance(sub, ast.Assign) \
                    and m.func_of.get(sub) is info.node \
                    and isinstance(sub.value, ast.Call):
                spec = self.donate_spec(sub.value, m)
                if spec is not None:
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            jit_locals[t.id] = spec
        result = None
        nums: Set[int] = set()
        names: Set[str] = set()
        for sub in ast.walk(info.node):
            if not isinstance(sub, ast.Return) or sub.value is None:
                continue
            if m.func_of.get(sub) is not info.node:
                continue
            spec = None
            if isinstance(sub.value, ast.Call):
                spec = self.donate_spec(sub.value, m)
            elif isinstance(sub.value, ast.Name):
                spec = jit_locals.get(sub.value.id)
            if spec is not None:
                nums.update(spec[0])
                names.update(spec[1])
        if nums or names:
            result = (tuple(sorted(nums)), tuple(sorted(names)))
        self._returns_donate[info.full] = result
        return result
