"""DT8xx — compile-cache key-stability rules.

The PR-16 fleet compile cache keys entries on ``sha256(lowered HLO text +
topology fingerprint + jax/jaxlib versions)``.  That key is only fleet-
stable if the lowered HLO is value-independent: a Python scalar or an
uncommitted host (numpy) array reaching a jit boundary as a leaf gets its
VALUE baked into the traced program on some paths (weak-type promotion,
committed-device defaults), producing per-value cache keys that no peer
ever hits — the exact "peer cache entries could never hit" engine bug the
PR-18 jit surgery fixed by funnelling every leaf through ``jnp.int32`` /
``jnp.asarray``.  These rules keep that property from regressing:

- **DT801** — a call site of a jit/CachedJit-routed callable passes a
  Python numeric literal (or a name bound to one / to a bare ``np.*``
  host-array constructor) as a non-static leaf argument.
- **DT802** — a jit/CachedJit is CONSTRUCTED inside a loop body
  (per-request / per-step retrace + cache-key churn).  The memoized
  per-bucket insert idiom (``self._decode_jit[key] = ...``) is exempt.

Both are per-module passes over the compile planes (serving/, models/,
elastic/); ``elastic/compile_cache.py`` itself is exempt as the defining
module.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from dstack_tpu.analysis.core import (
    Finding, Module, call_name, register,
)

SCOPE_PREFIXES = (
    "dstack_tpu/serving/", "dstack_tpu/models/", "dstack_tpu/elastic/",
)
DEFINING = ("dstack_tpu/elastic/compile_cache.py",)

#: call shapes that produce a compile-cache-routed (or plain jitted)
#: callable
_JIT_CONSTRUCTORS = ("jit", "pjit", "CachedJit", "maybe_cached",
                     "_jit_cached", "named_jit")
#: constructors that take the plain function and the jit keywords
#: themselves (``named_jit(fn, name, static_argnums=...)``), so the static
#: spec is read off their own call
_JIT_KEYWORD_CONSTRUCTORS = ("_jit_cached", "named_jit")
#: helpers that call a cached-jit callable for their caller: name ->
#: index of the first traced leaf (``_run_program(table, key, make,
#: *leaves)`` builds ``table[key]`` on first use and calls it on the leaves)
_JIT_RUNNERS = {"_run_program": 3}
#: numpy host-array constructors — uncommitted until device_put/jnp wraps
_NP_HOST = ("array", "zeros", "ones", "full", "asarray", "arange",
            "frombuffer", "load", "empty")
_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _last_part(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_jit_construct(call: ast.Call, mod: Module) -> bool:
    last = _last_part(call.func)
    if last not in _JIT_CONSTRUCTORS:
        return False
    if last in ("jit", "pjit"):
        # require the jax spelling so unrelated `.jit(...)` helpers
        # elsewhere never match
        qn = call_name(call, mod.aliases) or ""
        return qn in ("jax.jit", "jit", "pjit", "jax.pjit",
                      "jax.experimental.pjit.pjit")
    return True


def _inner_jit(call: ast.Call, mod: Module) -> Optional[ast.Call]:
    """The ``jax.jit(...)`` call inside ``maybe_cached(jax.jit(f), ...)``/
    ``CachedJit(jax.jit(f), ...)`` (or the call itself if it IS jax.jit)."""
    last = _last_part(call.func)
    if last in ("jit", "pjit") + _JIT_KEYWORD_CONSTRUCTORS:
        return call
    for a in call.args[:1]:
        if isinstance(a, ast.Call) and _is_jit_construct(a, mod):
            return a
    return None


def _static_spec(call: ast.Call, mod: Module) -> Tuple[Set[int], Set[str]]:
    """(static positional indices, static kwarg names) of the jit."""
    nums: Set[int] = set()
    names: Set[str] = set()
    inner = _inner_jit(call, mod)
    if inner is None:
        return nums, names
    for kw in inner.keywords:
        if kw.arg == "static_argnums":
            vals = (kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    nums.add(v.value)
        elif kw.arg == "static_argnames":
            vals = (kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    names.add(v.value)
    return nums, names


def _binding_key(target: ast.expr) -> Optional[str]:
    """Stable key for a jit-callable binding target: a plain name, a
    ``self.X`` attribute, or the dict behind ``self.X[k] = ...``."""
    if isinstance(target, ast.Name):
        return target.id
    if isinstance(target, ast.Attribute) and \
            isinstance(target.value, ast.Name) and \
            target.value.id in ("self", "cls"):
        return f"self.{target.attr}"
    if isinstance(target, ast.Subscript):
        return _binding_key(target.value)
    return None


def _call_key(func: ast.expr) -> Optional[str]:
    """Binding key a call site resolves against: ``fn(...)``,
    ``self.fn(...)``, ``self.table[k](...)``."""
    return _binding_key(func)


def _np_alias(mod: Module) -> Optional[str]:
    for alias, full in mod.aliases.items():
        if full == "numpy":
            return alias
    return None


def _is_np_host_call(expr: ast.AST, mod: Module) -> bool:
    if not isinstance(expr, ast.Call) or \
            not isinstance(expr.func, ast.Attribute):
        return False
    if expr.func.attr not in _NP_HOST:
        return False
    root = expr.func.value
    np_name = _np_alias(mod) or "np"
    return isinstance(root, ast.Name) and root.id == np_name


def _scalar_binding(mod: Module, fn: ast.AST, name: str) -> bool:
    """Every function-local binding of ``name`` is a Python numeric
    literal (may: a single such binding is enough to flag)."""
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign) and mod.func_of.get(n) is fn and \
                len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                n.targets[0].id == name and \
                isinstance(n.value, ast.Constant) and \
                isinstance(n.value.value, (int, float)) and \
                not isinstance(n.value.value, bool):
            return True
    return False


def _np_host_binding(mod: Module, fn: ast.AST, name: str) -> bool:
    """``name`` is bound to a bare np.* host constructor and never
    re-committed (device_put / jnp.asarray) before use."""
    host = False
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign) and mod.func_of.get(n) is fn and \
                len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                n.targets[0].id == name:
            if _is_np_host_call(n.value, mod):
                host = True
            else:
                return False  # re-bound to something else: stay silent
    if not host:
        return False
    for n in ast.walk(fn):
        if isinstance(n, ast.Call):
            last = _last_part(n.func)
            if last in ("device_put", "asarray", "int32", "int64",
                        "float32", "bfloat16"):
                if any(isinstance(a, ast.Name) and a.id == name
                       for a in n.args):
                    return False  # committed somewhere in this function
    return True


def _leaf_violation(arg: ast.expr, mod: Module,
                    fn: ast.AST) -> Optional[str]:
    if isinstance(arg, ast.Constant) and \
            isinstance(arg.value, (int, float)) and \
            not isinstance(arg.value, bool):
        return f"Python scalar literal {arg.value!r}"
    if _is_np_host_call(arg, mod):
        return "uncommitted np.* host array"
    if isinstance(arg, ast.Name) and fn is not None:
        if _scalar_binding(mod, fn, arg.id):
            return f"'{arg.id}' (bound to a Python scalar literal)"
        if _np_host_binding(mod, fn, arg.id):
            return (f"'{arg.id}' (bound to an uncommitted np.* host "
                    f"array)")
    return None


@register(
    "DT8xx",
    "DT801/DT802 compile-cache key stability: no Python-scalar or "
    "uncommitted-host leaves at jit/CachedJit call sites; no jit "
    "construction inside per-request/per-step loops",
)
def compile_stability(mod: Module) -> List[Finding]:
    if not any(mod.relpath.startswith(p) for p in SCOPE_PREFIXES):
        return []
    if any(mod.relpath.endswith(d) for d in DEFINING):
        return []
    findings: List[Finding] = []

    # pass 1: collect jit-callable bindings (+ static-arg specs) and
    # flag in-loop constructions
    bindings: Dict[str, Tuple[Set[int], Set[str]]] = {}
    for node in mod.nodes:
        if not (isinstance(node, ast.Call) and _is_jit_construct(node, mod)):
            continue
        parent = mod.parents.get(node)
        # walk out of wrapper constructors to the binding statement
        stmt: Optional[ast.AST] = node
        while stmt is not None and not isinstance(stmt, ast.stmt):
            stmt = mod.parents.get(stmt)
        if isinstance(stmt, ast.Assign):
            v = stmt.value
            # the binding must BE the constructor chain, not the result
            # of immediately calling it (params = jax.jit(init)())
            is_binding = v is node or (
                isinstance(v, ast.Call) and _is_jit_construct(v, mod)
                and _inner_jit(v, mod) is node)
            if is_binding:
                for t in stmt.targets:
                    key = _binding_key(t)
                    if key is not None:
                        nums, names = _static_spec(node, mod)
                        old = bindings.get(key)
                        if old is not None:
                            nums |= old[0]
                            names |= old[1]
                        bindings[key] = (nums, names)
        # DT802: construction inside a loop body (memoized subscript
        # insert is the sanctioned idiom and stays silent)
        if isinstance(parent, ast.Call) and _is_jit_construct(parent, mod):
            continue  # inner jax.jit of maybe_cached(...): flag once
        memoized = isinstance(stmt, ast.Assign) and any(
            isinstance(t, ast.Subscript) for t in stmt.targets)
        if not memoized:
            cur = stmt
            while cur is not None and not isinstance(cur, _FUNC_DEFS):
                par = mod.parents.get(cur)
                if isinstance(par, (ast.For, ast.While, ast.AsyncFor)) \
                        and cur is not getattr(par, "iter", None) \
                        and cur is not getattr(par, "test", None):
                    findings.append(mod.finding(
                        node, "DT802",
                        "jit/CachedJit constructed inside a loop body — "
                        "re-traces (and churns compile-cache keys) every "
                        "iteration; hoist it or memoize per bucket "
                        "(self._jits[key] = ...)",
                    ))
                    break
                cur = par
    # pass 2: call sites of the collected callables
    for node in mod.nodes:
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Call):
            # immediate invocation: jax.jit(init, ...)(...)
            if not _is_jit_construct(node.func, mod):
                continue
            nums, names = _static_spec(node.func, mod)
            key = "<immediate jit>"
            leaves = node.args
        elif _last_part(node.func) in _JIT_RUNNERS:
            key = _last_part(node.func)
            nums, names = set(), set()
            leaves = node.args[_JIT_RUNNERS[key]:]
        else:
            key = _call_key(node.func)
            if key is None or key not in bindings:
                continue
            if _is_jit_construct(node, mod):
                continue  # the construction itself, not a traced call
            nums, names = bindings[key]
            leaves = node.args
        fn = mod.func_of.get(node)
        for i, arg in enumerate(leaves):
            if i in nums:
                continue
            why = _leaf_violation(arg, mod, fn)
            if why is not None:
                findings.append(mod.finding(
                    arg, "DT801",
                    f"{why} passed as a traced leaf to cached-jit "
                    f"callable '{key}' — its value bakes into the "
                    f"lowered HLO, so the compile-cache key is "
                    f"per-value and peer cache entries can never hit; "
                    f"wrap it (jnp.int32/jnp.asarray/device_put) or "
                    f"mark it static",
                ))
        for kw in node.keywords:
            if kw.arg is None or kw.arg in names:
                continue
            why = _leaf_violation(kw.value, mod, fn)
            if why is not None:
                findings.append(mod.finding(
                    kw.value, "DT801",
                    f"{why} passed as traced kwarg '{kw.arg}' to "
                    f"cached-jit callable '{key}' — per-value compile-"
                    f"cache keys; wrap it or mark it static",
                ))
    return findings
