"""DT9xx — wirelint: cross-plane wire-contract analysis.

The planes of this system talk to each other over three informal
contracts that no type checker sees:

* **routes** — the control plane (aiohttp ``add_post``/``add_get``
  tables), the gateway, and the serving replica each register URL paths;
  the CLI/API client, the gateway's replica legs, the server's scrapers,
  and the tests call them back as string literals and f-string templates.
  A typo on either side ships silently and 404s in production.
* **internal headers** — the ``X-Dstack-*`` namespace (deadline budgets,
  trace propagation, the load piggyback, the PD phase tag) crosses every
  hop.  A header spelled slightly differently at one hop silently breaks
  deadline enforcement or leaks internal state to clients.
* **env knobs / metric families** — ``DSTACK_*`` variables are read at
  dozens of sites and metric families are recorded in one module but
  gated in another; both drift without a single source of truth.

wirelint extracts a **contract index** in one pass over the callgraph
project — registered routes, client path templates (resolved through
f-strings, local prefixes, and path-forwarding wrapper helpers like
``Client.project_post`` / ``fetch_replica_json``), env-knob read sites,
and recorded metric families — then cross-checks the sides:

* **DT901** — a client call names a root-relative path no plane
  registers (normalized over ``{placeholders}``; paths against a
  dynamic/external base are never judged).
* **DT902** — an ``X-Dstack-*`` header string literal outside
  ``serving/wire.py``, the single constants module every plane imports.
* **DT903** — a proxy leg copies upstream response headers into a client
  response without going through ``pd_protocol.copy_upstream_headers``
  — the one place that strips hop-by-hop and internal headers (the
  trace/load-header-leak incident class).
* **DT904** — a ``DSTACK_*`` env read that is missing from the
  ``core/knobs.py`` registry, or two read sites for the same knob with
  different literal defaults (default drift).
* **DT905** — a registered route with zero in-tree callers and no
  ``# dtlint: external-surface`` pragma on its registration line (dead
  or undocumented surface).
* **DT906** — a metric family recorded by ``telemetry/serving.py`` but
  absent from the ``scripts/check_metrics_exposition.py`` gate, or
  gated but never recorded.

MAY analysis throughout, like DT6xx/DT407: anything dynamic the resolver
cannot prove (an unresolvable base URL, a computed header name, a key
read through ``**kwargs``) stays silent rather than inventing findings.
When ``core/knobs.py`` or ``serving/wire.py`` are outside the scanned
set (file-scoped runs), the dependent rules stay silent the same way.

``python -m dstack_tpu.analysis.rules.wire_contracts <paths> --out f.json``
dumps the extracted contract inventory (routes / clients / headers /
knobs / metric families) — CI archives it next to dtlint-report.json.
"""

from __future__ import annotations

import ast
import json
import re
from pathlib import Path
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from dstack_tpu.analysis.callgraph import (
    FuncInfo,
    Project,
    Scope,
    qualified_name,
)
from dstack_tpu.analysis.core import Finding, Module, register_project

SCOPE_PREFIX = "dstack_tpu/"
EXEMPT_PREFIX = "dstack_tpu/analysis/"
WIRE_SUFFIX = "dstack_tpu/serving/wire.py"
KNOBS_SUFFIX = "dstack_tpu/core/knobs.py"
SERVING_TELEMETRY_SUFFIX = "dstack_tpu/telemetry/serving.py"
GATE_RELPATH = "scripts/check_metrics_exposition.py"

#: unresolvable-fragment marker inside a path template
DYN = "\x00"
#: param sentinel, used only during wrapper discovery: ``\x01name\x01``
_PS = "\x01"

_MAX_DEPTH = 6
_MAX_TEMPLATES = 16

#: HTTP-verb attributes whose first argument is the URL
_VERB_ARG0 = frozenset(
    {"get", "post", "put", "delete", "patch", "head", "options",
     "ws_connect"})
#: verb attributes whose SECOND argument is the URL (first is the method)
_VERB_ARG1 = frozenset({"request", "stream"})
#: receiver names that mark a call as an outbound HTTP call — ``get`` is
#: too common an attribute to accept on arbitrary receivers
_RECV_HINTS = frozenset(
    {"session", "sess", "_session", "client", "_client", "http", "_http",
     "httpx"})

#: aiohttp route-table registration attributes -> URL argument index
_ADD_VERBS = frozenset(
    {"add_get", "add_post", "add_put", "add_delete", "add_patch",
     "add_head"})
_WEB_VERBS = frozenset(
    {"get", "post", "put", "delete", "patch", "head", "view"})

_DSTACK_ENV_RE = re.compile(r"^DSTACK_[A-Z0-9_]+$")
_CATCH_SEG_RE = re.compile(r"\{[^}]*:[^}]*(?:\.\*|path)[^}]*\}")


# ---------------------------------------------------------------------------
# path-template resolution


def _concat(parts: List[Set[str]]) -> Set[str]:
    """Cartesian concatenation of string sets, giving up (-> {DYN}) when
    the product explodes."""
    out: Set[str] = {""}
    for p in parts:
        if not p:
            p = {DYN}
        nxt = {a + b for a in out for b in p}
        if len(nxt) > _MAX_TEMPLATES:
            return {DYN}
        out = nxt
    return out


class _Resolver:
    """Resolves an expression to the set of path-template strings it can
    evaluate to, with :data:`DYN` standing in for anything dynamic.

    Unlike ``Project.resolve_strs`` (which drops unresolvable branches
    entirely), templates must PRESERVE the position of the dynamic part:
    ``f"{p}/runs/list"`` with unresolvable ``p`` is still a useful
    template (``\\x00/runs/list``) because the literal tail identifies
    the route."""

    def __init__(self, project: Project) -> None:
        self.project = project
        self._visiting: Set[Tuple[int, str]] = set()

    def resolve(self, expr: Optional[ast.expr], scope: Scope,
                pmap: Optional[Dict[str, str]] = None,
                depth: int = 0) -> Set[str]:
        if expr is None or depth > _MAX_DEPTH:
            return {DYN}
        if isinstance(expr, ast.Constant):
            if isinstance(expr.value, str):
                return {expr.value}
            return {DYN}
        if isinstance(expr, ast.JoinedStr):
            parts: List[Set[str]] = []
            for v in expr.values:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    parts.append({v.value})
                elif isinstance(v, ast.FormattedValue):
                    parts.append(self.resolve(v.value, scope, pmap,
                                              depth + 1))
                else:
                    parts.append({DYN})
            return _concat(parts)
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            return _concat([self.resolve(expr.left, scope, pmap, depth + 1),
                            self.resolve(expr.right, scope, pmap,
                                         depth + 1)])
        if isinstance(expr, ast.IfExp):
            return (self.resolve(expr.body, scope, pmap, depth + 1)
                    | self.resolve(expr.orelse, scope, pmap, depth + 1))
        if isinstance(expr, ast.BoolOp):
            out: Set[str] = set()
            for v in expr.values:
                out |= self.resolve(v, scope, pmap, depth + 1)
            return out if len(out) <= _MAX_TEMPLATES else {DYN}
        if isinstance(expr, ast.Call):
            return self._resolve_call(expr, scope, pmap, depth)
        if isinstance(expr, ast.Name):
            return self._resolve_name(expr.id, scope, pmap, depth)
        if isinstance(expr, ast.Attribute):
            consts = self.project.resolve_strs(expr, scope)
            return set(consts) if consts else {DYN}
        return {DYN}

    def _resolve_call(self, call: ast.Call, scope: Scope,
                      pmap: Optional[Dict[str, str]],
                      depth: int) -> Set[str]:
        f = call.func
        # "".join-free string plumbing the clients actually use:
        # url.rstrip("/") + path, str(x)
        if isinstance(f, ast.Attribute) and f.attr in (
                "rstrip", "lstrip", "strip"):
            chars = None
            if call.args and isinstance(call.args[0], ast.Constant) \
                    and isinstance(call.args[0].value, str):
                chars = call.args[0].value
            elif call.args:
                return {DYN}
            base = self.resolve(f.value, scope, pmap, depth + 1)
            return {getattr(s, f.attr)(chars) if chars is not None
                    else getattr(s, f.attr)() for s in base}
        if isinstance(f, ast.Name) and f.id == "str" and len(call.args) == 1:
            return self.resolve(call.args[0], scope, pmap, depth + 1)
        return {DYN}

    def _resolve_name(self, name: str, scope: Scope,
                      pmap: Optional[Dict[str, str]],
                      depth: int) -> Set[str]:
        if pmap and name in pmap:
            return {pmap[name]}
        m = scope.module
        for i, fn in enumerate(scope.chain):
            inner = Scope(m, scope.chain[i:])
            values = self.project.local_assignments(fn).get(name)
            if values:
                out: Set[str] = set()
                for v in values:
                    out |= self.resolve(v, inner, pmap, depth + 1)
                return out if out and len(out) <= _MAX_TEMPLATES else {DYN}
            info = self.project.func_info(fn)
            if info is not None and any(
                    p.arg == name for p in info.all_params()):
                return self._resolve_param(info, name, depth)
        consts = self.project.resolve_strs(
            ast.Name(id=name, ctx=ast.Load()), scope)
        return set(consts) if consts else {DYN}

    def _resolve_param(self, info: FuncInfo, param: str,
                       depth: int) -> Set[str]:
        """Bind a parameter through the function's indexed call sites
        (Name / module-qualified calls only — attribute method calls are
        not indexed, which is exactly why wrappers are matched by NAME in
        :func:`_discover_wrappers`)."""
        key = (id(info.node), param)
        if key in self._visiting:
            return {DYN}
        self._visiting.add(key)
        try:
            out: Set[str] = set()
            default = info.param_default(param)
            if default is not None:
                out |= self.resolve(default, Scope(info.module, ()),
                                    None, depth + 1)
            pos = [p.arg for p in info.positional_params()]
            for call, site_scope, is_partial in self.project.call_sites(
                    info.full):
                bound: Optional[ast.expr] = None
                for kw in call.keywords:
                    if kw.arg == param:
                        bound = kw.value
                args = call.args[1:] if is_partial else call.args
                if bound is None and param in pos:
                    idx = pos.index(param)
                    if idx < len(args) and not any(
                            isinstance(a, ast.Starred)
                            for a in args[:idx + 1]):
                        bound = args[idx]
                if bound is not None:
                    out |= self.resolve(bound, site_scope, None, depth + 1)
                if len(out) > _MAX_TEMPLATES:
                    return {DYN}
            return out or {DYN}
        finally:
            self._visiting.discard(key)


# ---------------------------------------------------------------------------
# contract index


class _Route:
    __slots__ = ("module", "node", "path", "segs", "catch_idx", "dynamic")

    def __init__(self, module: Module, node: ast.AST, path: str) -> None:
        self.module = module
        self.node = node
        self.path = path
        self.segs = [s for s in path.split("?")[0].split("/") if s]
        self.catch_idx: Optional[int] = None
        for i, seg in enumerate(self.segs):
            if _CATCH_SEG_RE.search(seg):
                self.catch_idx = i
                break
        self.dynamic = DYN in path


class _ClientPath:
    __slots__ = ("module", "node", "segs", "open", "external", "display")

    def __init__(self, module: Module, node: ast.AST, segs: List[str],
                 open_tail: bool, external: bool, display: str) -> None:
        self.module = module
        self.node = node
        self.segs = segs
        self.open = open_tail
        self.external = external
        self.display = display


class _Wrapper:
    """A path-forwarding helper: a function whose body issues a client
    call whose URL ends with one of the function's own parameters —
    ``Client.post(path)``, ``Client.project_post(path)`` (prefix
    ``/api/project/{...}``), ``fetch_replica_json(session, urls, path)``.
    Call sites are matched by NAME because attribute method calls are
    invisible to the callgraph's call-site index."""

    __slots__ = ("name", "info", "param", "arg_index", "prefixes")

    def __init__(self, name: str, info: FuncInfo, param: str,
                 arg_index: Optional[int], prefixes: Set[str]) -> None:
        self.name = name
        self.info = info
        self.param = param
        self.arg_index = arg_index
        self.prefixes = prefixes


def _recv_hinted(func: ast.Attribute) -> bool:
    recv = func.value
    if isinstance(recv, ast.Name):
        return recv.id.lower() in _RECV_HINTS
    if isinstance(recv, ast.Attribute):
        return recv.attr.lower() in _RECV_HINTS
    return False


def _direct_url_expr(call: ast.Call) -> Optional[ast.expr]:
    """URL expression of a receiver-hinted outbound HTTP call, or None."""
    f = call.func
    if not isinstance(f, ast.Attribute) or not _recv_hinted(f):
        return None
    if f.attr in _VERB_ARG1 and len(call.args) >= 2:
        return call.args[1]
    if f.attr in _VERB_ARG0 and call.args:
        return call.args[0]
    return None


def _callee_tail(call: ast.Call) -> Optional[str]:
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    if isinstance(call.func, ast.Name):
        return call.func.id
    return None


def _url_candidates(
        call: ast.Call, wrappers: Dict[str, List[_Wrapper]],
) -> List[Tuple[ast.expr, Set[str]]]:
    """(url expr, prefix set) pairs for an outbound call: a direct
    client call contributes prefix ``""``; a wrapper call contributes
    the wrapper's discovered prefixes."""
    direct = _direct_url_expr(call)
    if direct is not None:
        return [(direct, {""})]
    return _wrapper_bindings(call, _callee_tail(call), wrappers)


def _wrapper_bindings(
        call: ast.Call, tail: Optional[str],
        wrappers: Dict[str, List[_Wrapper]],
) -> List[Tuple[ast.expr, Set[str]]]:
    """The wrapper-call half of :func:`_url_candidates`: bind the call's
    arguments against every known wrapper sharing the callee tail."""
    out: List[Tuple[ast.expr, Set[str]]] = []
    for w in wrappers.get(tail or "", ()):
        bound: Optional[ast.expr] = None
        for kw in call.keywords:
            if kw.arg == w.param:
                bound = kw.value
        if bound is None and w.arg_index is not None \
                and w.arg_index < len(call.args) and not any(
                    isinstance(a, ast.Starred)
                    for a in call.args[:w.arg_index + 1]):
            bound = call.args[w.arg_index]
        if bound is not None:
            out.append((bound, w.prefixes))
    return out


_PS_TAIL_RE = re.compile(r"^([^\x01]*)\x01(\w+)\x01$")


def _env_hinted(m: Module) -> bool:
    """Cheap substring gate before any per-node environment analysis: a
    module with neither token in its raw source cannot read os.environ
    under any alias (the binding site would have to spell one of them)."""
    return "environ" in m.source or "getenv" in m.source


def _index_fn_nodes(
        project: Project,
) -> Tuple[Dict[int, List[ast.Call]], Dict[int, List[ast.Subscript]]]:
    """id(function node) -> the Call / Subscript nodes anywhere inside
    it (nested defs included), built in one pass over the modules'
    pre-order node lists — re-walking every function AST per discovery
    round is what made the first cut of this pass blow the scan-time
    guard."""
    calls: Dict[int, List[ast.Call]] = {}
    subs: Dict[int, List[ast.Subscript]] = {}
    for m in project.modules:
        # the Subscript index only feeds env-helper discovery, whose
        # receivers all spell "env" somewhere (os.environ, getenv, or a
        # parameter named env/environ) — skip the rest of the tree
        want_subs = "env" in m.source
        for node in m.nodes:
            if isinstance(node, ast.Call):
                dest: Dict[int, list] = calls
            elif want_subs and isinstance(node, ast.Subscript):
                dest = subs
            else:
                continue
            fn = m.func_of.get(node)
            while fn is not None:
                dest.setdefault(id(fn), []).append(node)
                fn = m.func_of.get(fn)
    return calls, subs


def _discover_wrappers(
        project: Project, resolver: _Resolver,
        calls_by_fn: Dict[int, List[ast.Call]],
) -> Tuple[Dict[str, List[_Wrapper]], Set[int]]:
    """Fixpoint wrapper discovery; also returns the ids of each
    wrapper's own forwarding call so the collection pass does not count
    the wrapper body as a caller of its (unbound) template."""
    wrappers: Dict[str, List[_Wrapper]] = {}
    fwd_ids: Set[int] = set()
    infos = list({id(i): i for i in project.functions.values()}.values())
    # Per-function call facts, computed ONCE: (call, direct url expr,
    # callee tail, param map).  The fixpoint rounds below only re-do the
    # wrapper-name lookups against the growing wrapper set — re-deriving
    # receiver hints and callee tails for every call each round tripled
    # this pass's share of the scan-time budget.
    facts: Dict[int, List[Tuple[ast.Call, Optional[ast.expr],
                                Optional[str]]]] = {}
    pmaps: Dict[int, Dict[str, str]] = {}
    for info in infos:
        params = [p.arg for p in info.all_params()]
        if not params:
            continue
        flist = []
        for node in calls_by_fn.get(id(info.node), ()):
            direct = _direct_url_expr(node)
            tail = None if direct is not None else _callee_tail(node)
            if direct is None and tail is None:
                continue
            flist.append((node, direct, tail))
        if flist:
            facts[id(info.node)] = flist
            pmaps[id(info.node)] = {p: _PS + p + _PS for p in params}
    seen: Set[int] = set()
    for _ in range(4):
        added = False
        for info in infos:
            if id(info.node) in seen or id(info.node) not in facts:
                continue
            params = [p.arg for p in info.all_params()]
            pmap = pmaps[id(info.node)]
            for node, direct, tail in facts[id(info.node)]:
                if direct is not None:
                    candidates = [(direct, {""})]
                else:
                    candidates = _wrapper_bindings(node, tail, wrappers)
                for url_expr, prefixes in candidates:
                    scope = project.scope_at(info.module, node)
                    hit = False
                    for pref in prefixes:
                        for t in resolver.resolve(url_expr, scope, pmap):
                            m = _PS_TAIL_RE.match(pref + t)
                            if m is None or m.group(2) not in params:
                                continue
                            param = m.group(2)
                            pos = [p.arg
                                   for p in info.positional_params()]
                            arg_index = (pos.index(param)
                                         if param in pos else None)
                            name = info.qualname.split(".")[-1]
                            w = _Wrapper(name, info, param, arg_index,
                                         {m.group(1)})
                            for prev in wrappers.get(name, ()):
                                if prev.info is info:
                                    prev.prefixes |= w.prefixes
                                    break
                            else:
                                wrappers.setdefault(name, []).append(w)
                            fwd_ids.add(id(node))
                            seen.add(id(info.node))
                            hit = True
                            added = True
                    if hit:
                        break
        if not added:
            break
    return wrappers, fwd_ids


def _template_path(t: str) -> Optional[Tuple[str, bool]]:
    """Normalize a raw template to ``(absolute path, external_base)``.
    External = the path hangs off a scheme'd URL or a dynamic base (a
    replica/gateway/cloud endpoint) — usable for coverage, never for
    DT901."""
    for scheme in ("http://", "https://", "ws://", "wss://"):
        if t.startswith(scheme):
            rest = t[len(scheme):]
            i = rest.find("/")
            return (rest[i:], True) if i >= 0 else None
    if t.startswith(DYN):
        rest = t.lstrip(DYN)
        if not rest.startswith("/"):
            return None
        return rest, True
    if t.startswith("/"):
        return t, False
    return None


def _client_path(module: Module, node: ast.AST,
                 template: str) -> Optional[_ClientPath]:
    norm = _template_path(template)
    if norm is None:
        return None
    path, external = norm
    path = path.split("?")[0].split("#")[0]
    segs = [s for s in path.split("/") if s]
    open_tail = bool(segs) and DYN in segs[-1]
    display = path.replace(DYN, "{*}")
    return _ClientPath(module, node, segs, open_tail, external, display)


def _seg_match(rseg: str, cseg: str) -> bool:
    return (rseg.startswith("{") and rseg.endswith("}")) \
        or DYN in cseg or rseg == cseg


def _route_matches(route: _Route, segs: List[str]) -> bool:
    if route.catch_idx is not None:
        k = route.catch_idx
        if k == 0 or len(segs) < k:
            # a root catch-all (the gateway data plane) matches literally
            # anything — letting it satisfy DT901 would disable the rule
            return False
        return all(_seg_match(r, c)
                   for r, c in zip(route.segs[:k], segs[:k]))
    if len(route.segs) != len(segs):
        return False
    return all(_seg_match(r, c) for r, c in zip(route.segs, segs))


def _covers(route: _Route, cp: _ClientPath) -> bool:
    """Does this client template exercise this route (DT905 coverage)?
    Open templates (``f"{base}{path}"`` tails) prefix-match; closed
    templates must match exactly."""
    if cp.open:
        prefix = cp.segs[:-1]
        if not prefix or len(route.segs) < len(prefix):
            return False
        if DYN in prefix[0]:
            # fully-dynamic forwarding legs (``/{*}/{*}`` proxy paths)
            # would vacuously cover every route; only templates pinned by
            # a leading literal segment count as exercising a route
            return False
        return all(_seg_match(r, c)
                   for r, c in zip(route.segs[:len(prefix)], prefix))
    if route.catch_idx is not None:
        k = route.catch_idx
        return k > 0 and len(cp.segs) >= k and all(
            _seg_match(r, c) for r, c in zip(route.segs[:k], cp.segs[:k]))
    return _route_matches(route, cp.segs)


def _in_scope(relpath: str) -> bool:
    return relpath.startswith(SCOPE_PREFIX) \
        and not relpath.startswith(EXEMPT_PREFIX)


class ContractIndex:
    """Everything wirelint extracts in one pass: routes, client path
    templates, env-knob reads, the registry, metric families."""

    def __init__(self, project: Project) -> None:
        self.project = project
        self.resolver = _Resolver(project)
        self.routes: List[_Route] = []
        self.clients: List[_ClientPath] = []
        self.calls_by_fn, self.subs_by_fn = _index_fn_nodes(project)
        self.wrappers, self._fwd_ids = _discover_wrappers(
            project, self.resolver, self.calls_by_fn)
        for m in project.modules:
            self._extract_routes(m)
            self._extract_clients(m)

    # -- routes --------------------------------------------------------

    def _route_exprs(self, m: Module) -> Iterable[Tuple[ast.AST, ast.expr,
                                                        bool]]:
        """(anchor node, path expr, is_static) registration triples."""
        for node in m.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # FastAPI-style decorators: @app.get("/path")
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) \
                            and isinstance(dec.func, ast.Attribute) \
                            and dec.func.attr in _WEB_VERBS \
                            and isinstance(dec.func.value, ast.Name) \
                            and dec.func.value.id in ("app", "router") \
                            and dec.args:
                        yield dec, dec.args[0], False
                continue
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute):
                continue
            attr = node.func.attr
            if attr in _ADD_VERBS and node.args:
                yield node, node.args[0], False
            elif attr == "add_route" and len(node.args) >= 2:
                yield node, node.args[1], False
            elif attr == "add_static" and node.args:
                yield node, node.args[0], True
            elif attr in _WEB_VERBS or attr == "route":
                # web.get("/x", handler) route-table entries
                if isinstance(node.func.value, ast.Name) \
                        and node.func.value.id == "web" and node.args:
                    idx = 1 if attr == "route" else 0
                    if idx < len(node.args):
                        yield node, node.args[idx], False

    def _extract_routes(self, m: Module) -> None:
        if not m.relpath.startswith(SCOPE_PREFIX) \
                or m.relpath.startswith(EXEMPT_PREFIX):
            return
        for anchor, expr, is_static in self._route_exprs(m):
            scope = self.project.scope_at(m, anchor)
            for t in self.resolver.resolve(expr, scope):
                if not t.startswith("/"):
                    continue
                r = _Route(m, anchor, t)
                if is_static and r.catch_idx is None:
                    r.catch_idx = len(r.segs)
                self.routes.append(r)

    # -- clients -------------------------------------------------------

    def _extract_clients(self, m: Module) -> None:
        for node in m.nodes:
            if not isinstance(node, ast.Call) or id(node) in self._fwd_ids:
                continue
            for url_expr, prefixes in _url_candidates(node, self.wrappers):
                scope = self.project.scope_at(m, node)
                for pref in prefixes:
                    for t in self.resolver.resolve(url_expr, scope):
                        cp = _client_path(m, node, pref + t)
                        if cp is not None:
                            self.clients.append(cp)

    # -- lookups used by the rules and the inventory dump --------------

    def module_ending(self, suffix: str) -> Optional[Module]:
        for m in self.project.modules:
            if m.relpath.endswith(suffix):
                return m
        return None

    def tree_root(self) -> Optional[Path]:
        """Filesystem root of the scanned tree, recovered from any
        module whose absolute path ends with its relpath — how the
        metric gate script is located without global state."""
        for m in self.project.modules:
            sp = str(m.path)
            if sp.endswith(m.relpath):
                return Path(sp[:-len(m.relpath)] or ".")
        return None


# ---------------------------------------------------------------------------
# DT901 / DT905 — route <-> client cross-check


def _check_routes(idx: ContractIndex) -> Iterable[Finding]:
    # DT901 judges CALLS, not templates: a call reached through a
    # name-collided wrapper ("_request" exists on three client classes)
    # has several template interpretations — flag only when EVERY
    # interpretation is a closed root-relative path with no route match
    # (any external/open reading means the binding is ambiguous: MAY)
    by_call: Dict[int, List[_ClientPath]] = {}
    for cp in idx.clients:
        by_call.setdefault(id(cp.node), []).append(cp)
    for group in by_call.values():
        first = group[0]
        if not _in_scope(first.module.relpath):
            continue
        if any(cp.external or cp.open for cp in group):
            continue
        if any(_route_matches(r, cp.segs)
               for cp in group for r in idx.routes):
            continue
        yield first.module.finding(
            first.node, "DT901",
            f"client calls {first.display!r} but no plane registers that "
            "path — typo'd or removed route (routes are matched with "
            "{placeholder} segments as wildcards)")
    for r in idx.routes:
        if r.catch_idx is not None or r.dynamic \
                or not _in_scope(r.module.relpath):
            continue
        lines = range(r.node.lineno, getattr(r.node, "end_lineno",
                                             r.node.lineno) + 1)
        if any(ln in r.module.external_surface for ln in lines):
            continue
        if not any(_covers(r, cp) for cp in idx.clients):
            yield r.module.finding(
                r.node, "DT905",
                f"route {r.path!r} has no in-tree caller — dead surface, "
                "or an external contract that needs a "
                "'# dtlint: external-surface' pragma on the registration")


# ---------------------------------------------------------------------------
# DT902 — header literals outside serving/wire.py


def _is_docstring(m: Module, node: ast.AST) -> bool:
    parent = m.parents.get(node)
    if not isinstance(parent, ast.Expr):
        return False
    grand = m.parents.get(parent)
    body = getattr(grand, "body", None)
    return bool(body) and body[0] is parent


def _check_headers(project: Project) -> Iterable[Finding]:
    for m in project.modules:
        if not _in_scope(m.relpath) or m.relpath.endswith(WIRE_SUFFIX):
            continue
        for node in m.nodes:
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and node.value.lower().startswith("x-dstack")):
                continue
            if _is_docstring(m, node):
                continue
            yield m.finding(
                node, "DT902",
                f"internal header literal {node.value!r} — import the "
                "constant from dstack_tpu/serving/wire.py instead, so "
                "every hop spells the wire contract identically")


# ---------------------------------------------------------------------------
# DT903 — proxy legs must strip internal headers via copy_upstream_headers

_DT903_PREFIXES = ("dstack_tpu/gateway/", "dstack_tpu/server/routers/",
                   "dstack_tpu/serving/", "dstack_tpu/twin/")


def _attr_root(expr: ast.expr) -> Optional[str]:
    while isinstance(expr, ast.Attribute):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _headers_of(expr: ast.expr) -> Optional[str]:
    """Root variable name of an ``X.headers`` attribute chain, or of
    ``dict(X.headers)``; None when the expression is something else."""
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name) \
            and expr.func.id == "dict" and len(expr.args) == 1:
        expr = expr.args[0]
    if isinstance(expr, ast.Attribute) and expr.attr == "headers":
        return _attr_root(expr.value)
    return None


def _headers_items_src(expr: ast.expr) -> Optional[str]:
    """Root of ``X.headers.items()``, or None."""
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Attribute) \
            and expr.func.attr == "items":
        return _headers_of(expr.func.value)
    return None


_REQUEST_NAMES = frozenset({"request", "req", "self"})


def _fn_calls_copy_helper(m: Module, node: ast.AST) -> bool:
    fn = m.func_of.get(node)
    while fn is not None:
        if "copy_upstream_headers" in m.qualname.get(fn, fn.name):
            return True  # the helper's own implementation
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) \
                    and _callee_tail(sub) == "copy_upstream_headers":
                return True
        fn = m.func_of.get(fn)
    return False


def _check_header_leaks(project: Project) -> Iterable[Finding]:
    for m in project.modules:
        if not m.relpath.startswith(_DT903_PREFIXES):
            continue
        for node in m.nodes:
            src: Optional[str] = None
            if isinstance(node, (ast.For, ast.AsyncFor)):
                # for k, v in upstream.headers.items(): resp.headers[k]=v
                src = _headers_items_src(node.iter)
                if src is not None and not any(
                        isinstance(s, ast.Subscript)
                        and isinstance(s.value, ast.Attribute)
                        and s.value.attr == "headers"
                        for sub in node.body for s in ast.walk(sub)
                        if isinstance(s, ast.Subscript)):
                    src = None
            elif isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and f.attr == "update" \
                        and isinstance(f.value, ast.Attribute) \
                        and f.value.attr == "headers" and node.args:
                    # resp.headers.update(upstream.headers)
                    src = _headers_of(node.args[0])
                elif _callee_tail(node) in ("Response", "StreamResponse",
                                            "json_response"):
                    # web.StreamResponse(headers=upstream.headers)
                    for kw in node.keywords:
                        if kw.arg != "headers":
                            continue
                        src = _headers_of(kw.value)
                        if src is None and isinstance(kw.value,
                                                      ast.DictComp):
                            src = _headers_items_src(
                                kw.value.generators[0].iter)
            if src is None or src in _REQUEST_NAMES:
                continue
            if _fn_calls_copy_helper(m, node):
                continue
            yield m.finding(
                node, "DT903",
                f"response headers copied verbatim from {src!r} — route "
                "the leg through pd_protocol.copy_upstream_headers, which "
                "strips hop-by-hop and internal X-Dstack-* headers "
                "(trace/load header leak)")


# ---------------------------------------------------------------------------
# DT904 — env-knob registry and default drift


class _EnvRead:
    __slots__ = ("module", "node", "name", "default")

    def __init__(self, module: Module, node: ast.AST, name: str,
                 default: Tuple) -> None:
        self.module = module
        self.node = node
        self.name = name
        self.default = default  # ("num", x) | ("str", s) | ("absent",)
        #                         | ("unknown",)


def _registered_knobs(project: Project) -> Optional[Set[str]]:
    km = None
    for m in project.modules:
        if m.relpath.endswith(KNOBS_SUFFIX):
            km = m
            break
    if km is None:
        return None
    names: Set[str] = set()
    for node in km.nodes:
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "Knob" and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            names.add(node.args[0].value)
    return names


def _canon_default(value) -> Tuple:
    if isinstance(value, bool):
        return ("num", 1.0 if value else 0.0)
    if isinstance(value, (int, float)):
        return ("num", float(value))
    if isinstance(value, str):
        try:
            return ("num", float(value))
        except ValueError:
            return ("str", value)
    return ("unknown",)


def _fold_default(project: Project, m: Module, expr: Optional[ast.expr],
                  scope: Scope) -> Tuple:
    """Constant-fold a default expression to a comparable value; MAY —
    anything dynamic folds to ("unknown",) and never drifts."""
    if expr is None:
        return ("absent",)
    if isinstance(expr, ast.Constant):
        if expr.value is None:
            return ("absent",)
        return _canon_default(expr.value)
    if isinstance(expr, ast.Name):
        strs = project.resolve_strs(expr, scope)
        if len(strs) == 1:
            return _canon_default(next(iter(strs)))
        num = _module_num_const(project, m, expr.id)
        if num is not None:
            return ("num", num)
        return ("unknown",)
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name) \
            and expr.func.id in ("str", "int", "float") \
            and len(expr.args) == 1:
        return _fold_default(project, m, expr.args[0], scope)
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
        inner = _fold_default(project, m, expr.operand, scope)
        return ("num", -inner[1]) if inner[0] == "num" else ("unknown",)
    if isinstance(expr, ast.BinOp) and isinstance(expr.op,
                                                  (ast.Add, ast.Mult)):
        left = _fold_default(project, m, expr.left, scope)
        right = _fold_default(project, m, expr.right, scope)
        if left[0] == right[0] == "num":
            v = (left[1] + right[1] if isinstance(expr.op, ast.Add)
                 else left[1] * right[1])
            return ("num", v)
        return ("unknown",)
    return ("unknown",)


def _module_num_const(project: Project, m: Module,
                      name: str) -> Optional[float]:
    """Module-level numeric constant (DEFAULT_COORDINATOR_PORT = 8476),
    following one import hop — str_consts only carries strings."""
    target = m
    full = m.aliases.get(name)
    if full is not None and "." in full:
        mod_path, name = full.rsplit(".", 1)
        hit = project.by_relpath.get(mod_path.replace(".", "/") + ".py")
        if hit is not None:
            target = hit
    for stmt in target.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == name \
                and isinstance(stmt.value, ast.Constant) \
                and isinstance(stmt.value.value, (int, float)) \
                and not isinstance(stmt.value.value, bool):
            return float(stmt.value.value)
    return None


def _env_alias_names(project: Project, scope: Scope) -> Set[str]:
    """Local names bound (possibly conditionally) to os.environ in the
    enclosing function chain: ``env = os.environ if env is None else
    env`` and friends."""
    out: Set[str] = set()
    for fn in scope.chain:
        for name, values in project.local_assignments(fn).items():
            for v in values:
                for sub in ast.walk(v):
                    if isinstance(sub, ast.Attribute) and qualified_name(
                            sub, scope.module.aliases) == "os.environ":
                        out.add(name)
    return out


def _direct_env_reads(project: Project,
                      m: Module) -> Iterable[Tuple[ast.AST, ast.expr,
                                                   Optional[ast.expr]]]:
    """(node, key expr, default expr) for every direct os.environ read:
    os.environ.get / os.getenv / os.environ[...] / alias.get where the
    alias is locally bound to os.environ.  Plain-dict ``env.get`` on a
    job-env mapping never matches — the receiver must trace to
    os.environ."""
    if not _env_hinted(m):
        return
    for node in m.nodes:
        if isinstance(node, ast.Subscript):
            if qualified_name(node.value, m.aliases) == "os.environ":
                yield node, node.slice, None
            continue
        if not isinstance(node, ast.Call):
            continue
        qn = qualified_name(node.func, m.aliases)
        if qn in ("os.environ.get", "os.getenv") and node.args:
            yield (node, node.args[0],
                   node.args[1] if len(node.args) > 1 else None)
            continue
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" \
                and isinstance(node.func.value, ast.Name) and node.args:
            scope = project.scope_at(m, node)
            if node.func.value.id in _env_alias_names(project, scope):
                yield (node, node.args[0],
                       node.args[1] if len(node.args) > 1 else None)


def _env_helpers(idx: "ContractIndex") -> List[Tuple[FuncInfo, str, str]]:
    """(helper, key param, default param) for partial-bound env helpers:
    a function reading os.environ (or a param named env/environ) with
    the KEY taken from its own parameter — settings._env/_env_bool,
    routing._env_float."""
    project = idx.project
    out: List[Tuple[FuncInfo, str, str]] = []
    for info in {id(i): i for i in project.functions.values()}.values():
        params = {p.arg for p in info.all_params()}
        if not params:
            continue
        if not _env_hinted(info.module) \
                and not (params & {"env", "environ"}):
            continue  # no receiver in this function can be os.environ
        m = info.module
        for node in (*idx.calls_by_fn.get(id(info.node), ()),
                     *idx.subs_by_fn.get(id(info.node), ())):
            if isinstance(node, ast.Subscript):
                recv_qn = qualified_name(node.value, m.aliases)
                recv_param = (node.value.id
                              if isinstance(node.value, ast.Name) else None)
                key = node.slice
                default = None
            else:
                f = node.func
                if not (isinstance(f, ast.Attribute) and f.attr == "get"
                        and node.args):
                    continue
                recv_qn = qualified_name(f, m.aliases)
                recv_qn = "os.environ" if recv_qn == "os.environ.get" \
                    else None
                recv_param = (f.value.id
                              if isinstance(f.value, ast.Name) else None)
                key = node.args[0]
                default = node.args[1] if len(node.args) > 1 else None
            env_recv = recv_qn == "os.environ" or (
                recv_param in params
                and recv_param in ("env", "environ"))
            if not env_recv:
                continue
            if not (isinstance(key, ast.Name) and key.id in params):
                continue
            if "default" in params:
                dparam = "default"
            elif isinstance(default, ast.Name) and default.id in params:
                dparam = default.id
            else:
                dparam = ""
            out.append((info, key.id, dparam))
            break
    return out


def _collect_env_reads(idx: ContractIndex) -> List[_EnvRead]:
    project = idx.project
    reads: List[_EnvRead] = []

    def add(m: Module, node: ast.AST, key_expr: ast.expr,
            default: Tuple) -> None:
        scope = project.scope_at(m, node)
        for name in project.resolve_strs(key_expr, scope) or (
                {key_expr.value} if isinstance(key_expr, ast.Constant)
                and isinstance(key_expr.value, str) else set()):
            if _DSTACK_ENV_RE.match(name):
                reads.append(_EnvRead(m, node, name, default))

    helper_nodes: Set[int] = set()
    for info, key_param, dparam in _env_helpers(idx):
        helper_nodes.add(id(info.node))
        pos = [p.arg for p in info.positional_params()]
        for call, site_scope, is_partial in project.call_sites(info.full):
            sm = site_scope.module
            if not _in_scope(sm.relpath) or sm.relpath.endswith(
                    KNOBS_SUFFIX):
                continue
            args = call.args[1:] if is_partial else call.args
            bound: Dict[str, ast.expr] = {
                kw.arg: kw.value for kw in call.keywords if kw.arg}
            for i, a in enumerate(args):
                if isinstance(a, ast.Starred):
                    break
                if i < len(pos):
                    bound.setdefault(pos[i], a)
            key_expr = bound.get(key_param)
            if key_expr is None:
                continue
            default_expr = bound.get(dparam) if dparam else None
            if default_expr is None and dparam:
                default_expr = info.param_default(dparam)
            folded = _fold_default(project, sm, default_expr, site_scope)
            add(sm, call, key_expr, folded)

    for m in project.modules:
        if not _in_scope(m.relpath) or m.relpath.endswith(KNOBS_SUFFIX):
            continue
        for node, key_expr, default_expr in _direct_env_reads(project, m):
            fn = m.func_of.get(node)
            if fn is not None and id(fn) in helper_nodes:
                continue  # the helper body itself: sites carry the reads
            scope = project.scope_at(m, node)
            folded = _fold_default(project, m, default_expr, scope)
            add(m, node, key_expr, folded)
    return reads


def _fmt_default(d: Tuple) -> str:
    if d[0] == "num":
        v = d[1]
        return str(int(v)) if v == int(v) else str(v)
    return repr(d[1])


def _check_env_knobs(idx: ContractIndex) -> Iterable[Finding]:
    registered = _registered_knobs(idx.project)
    if registered is None:
        return  # knobs registry outside the scanned set: stay silent
    reads = _collect_env_reads(idx)
    by_name: Dict[str, List[_EnvRead]] = {}
    for r in reads:
        by_name.setdefault(r.name, []).append(r)
    for name, sites in sorted(by_name.items()):
        if name not in registered:
            for r in sites:
                yield r.module.finding(
                    r.node, "DT904",
                    f"env knob {name!r} is not declared in "
                    "core/knobs.py — register it (name, default, parser, "
                    "doc) so docs and speclint see it")
            continue
        concrete = [r for r in sites if r.default[0] in ("num", "str")]
        values = {r.default for r in concrete}
        if len(values) > 1:
            listing = ", ".join(sorted(_fmt_default(v) for v in values))
            for r in concrete:
                yield r.module.finding(
                    r.node, "DT904",
                    f"env knob {name!r} read with default "
                    f"{_fmt_default(r.default)} here but other sites use "
                    f"a different one ({listing}) — defaults drift; hoist "
                    "the value into core/knobs.py and read it once")


# ---------------------------------------------------------------------------
# DT906 — recorded metric families vs the exposition gate

_METRIC_PREFIX = "dstack_serving_"
_GATE_SUFFIXES = ("_bucket", "_count", "_sum")


def _base_family(name: str) -> str:
    for suf in _GATE_SUFFIXES:
        if name.endswith(suf):
            return name[:-len(suf)]
    return name


def _recorded_families(idx: ContractIndex,
                       tm: Module) -> Dict[str, ast.AST]:
    project = idx.project
    out: Dict[str, ast.AST] = {}
    for node in tm.nodes:
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("histogram", "gauge", "counter")
                and node.args):
            continue
        arg = node.args[0]
        name: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        elif isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add) \
                and isinstance(arg.right, ast.Constant) \
                and isinstance(arg.right.value, str):
            scope = project.scope_at(tm, node)
            prefixes = project.resolve_strs(arg.left, scope)
            if len(prefixes) == 1:
                name = next(iter(prefixes)) + arg.right.value
        if name is not None and name.startswith(_METRIC_PREFIX):
            out.setdefault(name, node)
    return out


def _gated_families(root: Path, recorded) -> Optional[Set[str]]:
    """Families the gate script asserts on /metrics.  A gated sample name
    stands for the family of that very name where one is ``recorded`` (a
    counter may end in ``_sum``), else for its histogram
    (``_bucket``/``_count``/``_sum`` taken off)."""
    gate = root / GATE_RELPATH
    try:
        tree = ast.parse(gate.read_text())
    except (OSError, SyntaxError):
        return None
    names = {node.value for node in ast.walk(tree)
             if isinstance(node, ast.Constant) and isinstance(node.value, str)
             and node.value.startswith(_METRIC_PREFIX)}
    return {n if n in recorded else _base_family(n) for n in names}


def _check_metric_families(idx: ContractIndex) -> Iterable[Finding]:
    tm = idx.module_ending(SERVING_TELEMETRY_SUFFIX)
    root = idx.tree_root()
    if tm is None or root is None:
        return
    recorded = _recorded_families(idx, tm)
    gated = _gated_families(root, recorded)
    if gated is None:
        return  # no gate script next to the tree: file-scoped run
    for name, node in sorted(recorded.items()):
        if name not in gated:
            yield tm.finding(
                node, "DT906",
                f"metric family {name!r} is recorded but "
                f"{GATE_RELPATH} never asserts it on /metrics — the "
                "exposition gate no longer covers it")
    for name in sorted(gated - set(recorded)):
        yield tm.finding(
            tm.tree, "DT906",
            f"{GATE_RELPATH} gates metric family {name!r} but "
            "telemetry/serving.py never records it — stale gate entry "
            "or a renamed family")


# ---------------------------------------------------------------------------
# registration + inventory


@register_project(
    "DT9xx",
    "wirelint: cross-plane wire contracts — DT901 client path without a "
    "registered route; DT902 X-Dstack-* header literal outside "
    "serving/wire.py; DT903 proxy leg bypassing copy_upstream_headers; "
    "DT904 unregistered or default-drifting DSTACK_* env knob; DT905 "
    "registered route with no in-tree caller and no external-surface "
    "pragma; DT906 recorded metric family missing from the exposition "
    "gate (or vice versa)",
)
def check(project: Project) -> Iterable[Finding]:
    idx = ContractIndex(project)
    out: List[Finding] = []
    seen: Set[Tuple] = set()
    for f in (*_check_routes(idx), *_check_headers(project),
              *_check_header_leaks(project), *_check_env_knobs(idx),
              *_check_metric_families(idx)):
        key = (f.path, f.line, f.col, f.code, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def contract_inventory(project: Project) -> Dict:
    """The extracted wire-contract inventory, JSON-shaped — CI archives
    this next to dtlint-report.json so a reviewer can diff the actual
    cross-plane surface a PR adds or removes."""
    idx = ContractIndex(project)
    routes = sorted({(r.path, r.module.relpath, r.node.lineno)
                     for r in idx.routes})
    clients = sorted({(c.display, c.module.relpath, c.node.lineno)
                      for c in idx.clients if c.segs})
    headers: List[Dict] = []
    wm = idx.module_ending(WIRE_SUFFIX)
    if wm is not None:
        for stmt in wm.tree.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and isinstance(stmt.value, ast.Constant) \
                    and isinstance(stmt.value.value, str):
                headers.append({"constant": stmt.targets[0].id,
                                "value": stmt.value.value})
    knobs: List[Dict] = []
    km = idx.module_ending(KNOBS_SUFFIX)
    if km is not None:
        for node in km.nodes:
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "Knob" and node.args \
                    and isinstance(node.args[0], ast.Constant):
                entry: Dict = {"name": node.args[0].value}
                for kw in node.keywords:
                    if kw.arg in ("default", "parser", "plane",
                                  "injected") and isinstance(
                                      kw.value, ast.Constant):
                        entry[kw.arg] = kw.value.value
                knobs.append(entry)
    tm = idx.module_ending(SERVING_TELEMETRY_SUFFIX)
    root = idx.tree_root()
    recorded = sorted(_recorded_families(idx, tm)) if tm else []
    gated = sorted(_gated_families(root, recorded) or ()) if root else []
    return {
        "routes": [{"path": p, "file": f, "line": ln}
                   for p, f, ln in routes],
        "clients": [{"path": p, "file": f, "line": ln}
                    for p, f, ln in clients],
        "headers": headers,
        "knobs": knobs,
        "metrics": {"recorded": recorded, "gated": gated},
    }


def main(argv: Optional[List[str]] = None) -> int:
    """Dump the contract inventory for CI archival."""
    import argparse

    from dstack_tpu.analysis.core import iter_python_files, load_module

    ap = argparse.ArgumentParser(
        prog="python -m dstack_tpu.analysis.rules.wire_contracts",
        description="extract the wire-contract inventory as JSON")
    ap.add_argument("paths", nargs="+", type=Path)
    ap.add_argument("--out", type=Path, default=None,
                    help="write JSON here (default: stdout)")
    ns = ap.parse_args(argv)
    modules = []
    for path in iter_python_files(ns.paths):
        try:
            modules.append(load_module(path))
        except (OSError, SyntaxError):
            continue
    inv = contract_inventory(Project(modules))
    text = json.dumps(inv, indent=2, sort_keys=True)
    if ns.out is not None:
        ns.out.write_text(text + "\n")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
