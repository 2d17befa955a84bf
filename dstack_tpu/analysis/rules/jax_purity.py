"""DT3xx — JAX trace purity inside jit/shard_map-compiled functions.

Scope: the compute plane (``dstack_tpu/models|ops|parallel|serving``).
A "traced function" is one decorated with ``jax.jit``/``shard_map``/
``pjit``/``pmap`` (directly or via ``functools.partial``), one passed by
name into such a call anywhere in the module (the
``step_fn = jax.jit(step, ...)`` idiom ``make_train_step`` uses), or —
transitively — any same-module function called from a traced one.

DT301  Python ``if``/``while`` branching on a runtime VALUE of a traced
       parameter — a silent recompile per distinct value, or a
       ConcretizationTypeError.  Shape/dtype/None tests are static and
       exempt (``x.shape``, ``x.ndim``, ``x.dtype``, ``len(x)``,
       ``x is None``, ``isinstance``).
DT302  host sync inside the trace: ``float()``/``int()``/``bool()`` on a
       non-static expression, ``.item()``, ``np.asarray``/``np.array``,
       ``jax.device_get`` — each blocks dispatch to pull the value back.
DT303  ``print`` inside the trace: fires once at trace time, never per
       step — use ``jax.debug.print``.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from dstack_tpu.analysis.core import (
    Finding,
    Module,
    call_name,
    qualified_name,
    register,
)

SCOPE_PREFIXES = (
    "dstack_tpu/models/",
    "dstack_tpu/ops/",
    "dstack_tpu/parallel/",
    "dstack_tpu/serving/",
)

TRACER_ENTRY_POINTS = {
    "jax.jit", "jit", "pjit", "jax.pmap", "pmap",
    "shard_map", "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "jax.experimental.shard_map",
    # the serving engine's naming wrappers around jax.jit
    # (utils/jax_runtime.py named_jit, InferenceEngine._jit_cached): their
    # first argument is the function that gets traced
    "named_jit", "self._jit_cached",
}

#: attribute reads on a traced array that are static at trace time
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}

HOST_SYNC_CALLS = {
    "numpy.asarray", "numpy.array", "np.asarray", "np.array",
    "jax.device_get",
}


def _entry_point_name(mod: Module, expr: ast.expr) -> Optional[str]:
    """Resolve a decorator/callee expression to a tracer entry point,
    looking through ``functools.partial(jax.jit, ...)``."""
    if isinstance(expr, ast.Call):
        name = call_name(expr, mod.aliases)
        if name in ("functools.partial", "partial") and expr.args:
            return _entry_point_name(mod, expr.args[0])
        if name in TRACER_ENTRY_POINTS:
            return name
        return None
    name = qualified_name(expr, mod.aliases)
    return name if name in TRACER_ENTRY_POINTS else None


def _traced_functions(mod: Module) -> Set[ast.AST]:
    """Directly-traced defs plus the same-module transitive closure of
    functions they call by name."""
    by_name = {}
    for node in mod.nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    traced: Set[ast.AST] = set()
    for node in mod.nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if _entry_point_name(mod, deco):
                    traced.add(node)
        elif isinstance(node, ast.Call):
            if _entry_point_name(mod, node.func) and node.args and isinstance(
                node.args[0], ast.Name
            ):
                traced.update(by_name.get(node.args[0].id, []))
    # transitive: f called by name from a traced function's body
    changed = True
    while changed:
        changed = False
        for fn in list(traced):
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call) and isinstance(
                    sub.func, ast.Name
                ):
                    for cand in by_name.get(sub.func.id, []):
                        if cand not in traced:
                            traced.add(cand)
                            changed = True
    return traced


#: annotation substrings that mark a parameter as an array (traced); any
#: OTHER annotation (int, str, LlamaConfig, ShardingPolicy, ...) marks it
#: static — annotating scalar/config params is the conventional way to
#: tell dtlint (and readers) the value is fixed at trace time
ARRAY_ANNOTATIONS = ("Array", "ndarray", "Tensor", "ArrayLike")


def _param_names(fn: ast.AST) -> Set[str]:
    """Potentially-traced parameters: unannotated or array-annotated."""
    out: Set[str] = set()
    a = fn.args
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        if p.arg in ("self", "cls"):
            continue
        if p.annotation is not None:
            ann = ast.unparse(p.annotation)
            if not any(tok in ann for tok in ARRAY_ANNOTATIONS):
                continue  # annotated non-array -> static by convention
        out.add(p.arg)
    # *args/**kwargs are deliberately NOT included: the containers' own
    # truthiness/len are static at trace time (`if kwargs: raise ...` is a
    # standard guard), and element-wise hazards through them are rare
    # enough that a pragma on the odd real one beats flagging every guard
    return out


def _tainted_names(fn: ast.AST, params: Set[str]) -> Set[str]:
    """Params plus locals (transitively) assigned from expressions that
    reference them — a cheap forward taint pass, iterated to fixpoint so
    assignment order doesn't matter."""
    tainted = set(params)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            if not _refs_param_value(node.value, tainted):
                continue
            for t in node.targets:
                for n in ast.walk(t):
                    if (isinstance(n, ast.Name)
                            and isinstance(n.ctx, ast.Store)
                            and n.id not in tainted):
                        tainted.add(n.id)
                        changed = True
    return tainted


def _refs_param_value(e: ast.expr, params: Set[str]) -> bool:
    """True when ``e`` consumes a parameter's runtime VALUE (as opposed to
    its static shape/dtype metadata)."""
    if isinstance(e, ast.Name):
        return e.id in params
    if isinstance(e, ast.Attribute):
        if e.attr in STATIC_ATTRS:
            return False
        return _refs_param_value(e.value, params)
    if isinstance(e, ast.Subscript):
        return _refs_param_value(e.value, params)
    if isinstance(e, ast.Call):
        if isinstance(e.func, ast.Name):
            if e.func.id in ("len", "isinstance", "getattr", "hasattr",
                             "type"):
                return False
            return any(_refs_param_value(a, params) for a in e.args)
        if isinstance(e.func, ast.Attribute):
            # method on a param (batch.get(...)) yields a runtime value
            return (_refs_param_value(e.func.value, params)
                    or any(_refs_param_value(a, params) for a in e.args))
        return any(_refs_param_value(a, params) for a in e.args)
    if isinstance(e, ast.BinOp):
        return (_refs_param_value(e.left, params)
                or _refs_param_value(e.right, params))
    if isinstance(e, ast.UnaryOp):
        return _refs_param_value(e.operand, params)
    if isinstance(e, (ast.Tuple, ast.List)):
        return any(_refs_param_value(x, params) for x in e.elts)
    return False


def _test_is_traced_hazard(e: ast.expr, params: Set[str]) -> bool:
    if isinstance(e, ast.BoolOp):
        return any(_test_is_traced_hazard(v, params) for v in e.values)
    if isinstance(e, ast.UnaryOp) and isinstance(e.op, ast.Not):
        return _test_is_traced_hazard(e.operand, params)
    if isinstance(e, ast.Compare):
        # `x is None` and `"key" in params_dict` are structure tests,
        # static at trace time
        if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
               for op in e.ops):
            return False
        return any(_refs_param_value(x, params)
                   for x in [e.left, *e.comparators])
    return _refs_param_value(e, params)


def _static_expr(e: ast.expr) -> bool:
    """Trace-time constants: literals, shape/len arithmetic."""
    if isinstance(e, ast.Constant):
        return True
    if isinstance(e, ast.Attribute):
        return e.attr in STATIC_ATTRS
    if isinstance(e, ast.Subscript):
        return _static_expr(e.value)
    if isinstance(e, ast.Call) and isinstance(e.func, ast.Name):
        return e.func.id == "len"
    if isinstance(e, ast.BinOp):
        return _static_expr(e.left) and _static_expr(e.right)
    if isinstance(e, ast.UnaryOp):
        return _static_expr(e.operand)
    return False


@register("DT3xx", "JAX trace purity in jit/shard_map-compiled functions")
def check(mod: Module) -> Iterable[Finding]:
    if not any(p in mod.relpath for p in SCOPE_PREFIXES):
        return []
    out: List[Finding] = []
    for fn in _traced_functions(mod):
        params = _tainted_names(fn, _param_names(fn))
        for node in ast.walk(fn):
            # don't descend into nested defs twice — nested defs that are
            # themselves traced appear in _traced_functions via closure
            if isinstance(node, (ast.If, ast.While)):
                if mod.func_of.get(node) is not fn:
                    continue
                if _test_is_traced_hazard(node.test, params):
                    kind = "while" if isinstance(node, ast.While) else "if"
                    out.append(mod.finding(
                        node, "DT301",
                        f"Python `{kind}` on a traced value inside a "
                        "jit/shard_map function — recompile per value or "
                        "ConcretizationTypeError; use jnp.where / "
                        "lax.cond / lax.while_loop",
                    ))
            elif isinstance(node, ast.Call):
                if mod.func_of.get(node) is not fn:
                    continue
                name = call_name(node, mod.aliases) or ""
                if (isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "int", "bool")
                        and node.args
                        and not _static_expr(node.args[0])
                        and _refs_param_value(node.args[0], params)):
                    out.append(mod.finding(
                        node, "DT302",
                        f"`{node.func.id}()` on a traced value inside a "
                        "jit/shard_map function forces a host sync "
                        "(ConcretizationTypeError under jit)",
                    ))
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr == "item"
                      and not node.args
                      and _refs_param_value(node.func.value, params)):
                    out.append(mod.finding(
                        node, "DT302",
                        "`.item()` inside a jit/shard_map function forces "
                        "a host sync",
                    ))
                elif name in HOST_SYNC_CALLS and any(
                    _refs_param_value(a, params) for a in node.args
                ):
                    out.append(mod.finding(
                        node, "DT302",
                        f"`{name}` inside a jit/shard_map function pulls "
                        "the array to host memory",
                    ))
                elif name == "print":
                    out.append(mod.finding(
                        node, "DT303",
                        "`print` inside a jit/shard_map function fires at "
                        "trace time only — use jax.debug.print",
                    ))
    return out
