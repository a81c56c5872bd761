"""donation-safety: reads of a buffer after it flowed into a donated
jit argument.

``donate_argnums`` lets XLA alias an input buffer into an output
(weight-update aliasing, arXiv:2004.13336); touching the donated array
afterwards is undefined behavior — jax *may* raise a deleted-buffer error,
or silently read garbage on some backends. The pass learns which callables
donate from two sources:

  - local ``name = jax.jit(f, donate_argnums=(...))`` bindings (also
    ``@functools.partial(jax.jit, donate_argnums=...)`` decorators);
  - the framework's own ``@_update_kernel(a, b, ...)`` optimizer-kernel
    decorator (optimizer/optimizer.py) and its flat-bucket analog
    ``@_sharded_update_kernel(a, ...)`` (parallel/zero.py), whose
    positions ARE donate_argnums. A read of the donated bucket — or of
    any view sliced out of it, since a subscript read loads the base
    name — after the call is flagged.

At each call of a known donor it records the argument expressions sitting in
donated positions, then flags any later *read* of the same expression in the
enclosing body. A store to the expression (including tuple-unpack targets of
the donating call itself) or a framework ``x._set_data(...)`` — which swaps
in a fresh buffer for ``x._data`` — ends the hazard window.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from ..core import (Finding, ModuleInfo, call_name, register_pass, unparse)


def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """donate_argnums of a jax.jit(...) call, if present."""
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            nums = [n.value for n in ast.walk(kw.value)
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, int)]
            return tuple(nums)
    return None


def _collect_donors(mod: ModuleInfo) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    """scope-qualname -> {donor name -> donated positions}. A ``fn =
    jax.jit(...)`` binding is only a donor within the function that made it
    (and its nested defs) — an unrelated local also named ``fn`` in another
    method must not inherit it. Scope '' is module level."""
    donors: Dict[str, Dict[str, Tuple[int, ...]]] = {}

    def _scope_of(node) -> str:
        fn = mod.enclosing_function(node)
        return mod.qualname(fn) if fn is not None else ""

    for node in ast.walk(mod.tree):
        # fn = jax.jit(body, donate_argnums=(0, 1))
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if call_name(call) in ("jit", "pjit"):
                pos = _donated_positions(call)
                if pos:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            donors.setdefault(_scope_of(node), {})[t.id] = pos
        # @partial(jax.jit, donate_argnums=...) / @_update_kernel(0, 2)
        # / @_sharded_update_kernel(0)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                name = call_name(dec)
                pos = None
                if name == "partial" and dec.args \
                        and unparse(dec.args[0]).endswith("jit"):
                    pos = _donated_positions(dec)
                elif name in ("_update_kernel", "_sharded_update_kernel"):
                    pos = tuple(a.value for a in dec.args
                                if isinstance(a, ast.Constant)
                                and isinstance(a.value, int))
                if pos:
                    donors.setdefault(_scope_of(node), {})[node.name] = pos
    return donors


def _visible_donors(scoped: Dict[str, Dict[str, Tuple[int, ...]]],
                    qn: str) -> Dict[str, Tuple[int, ...]]:
    """Donors visible from scope `qn`: module level plus every enclosing
    scope prefix (closure visibility)."""
    out = dict(scoped.get("", {}))
    parts = qn.split(".") if qn else []
    for i in range(1, len(parts) + 1):
        out.update(scoped.get(".".join(parts[:i]), {}))
    return out


def _is_trackable(expr: ast.AST) -> bool:
    """Only track plain names / attribute chains — calls and literals have
    no later-read identity."""
    while isinstance(expr, ast.Attribute):
        expr = expr.value
    return isinstance(expr, ast.Name)


class _Hazard:
    __slots__ = ("expr", "donor", "line")

    def __init__(self, expr: str, donor: str, line: int):
        self.expr = expr
        self.donor = donor
        self.line = line


def _store_targets(stmt: ast.stmt) -> List[str]:
    """Unparsed store-context targets of a statement (incl. tuple unpack)."""
    out: List[str] = []
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign, ast.For)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.Delete):
        targets = list(stmt.targets)
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            out.extend(unparse(e) for e in t.elts)
        else:
            out.append(unparse(t))
    return out


def _walk_shallow(node: ast.AST):
    """ast.walk that does not descend into nested function/class bodies —
    a read inside a nested def executes when the def is *called*, not at
    this point in the enclosing body (nested defs are checked on their
    own via mod.functions())."""
    stack = [node]
    while stack:
        cur = stack.pop()
        yield cur
        for child in ast.iter_child_nodes(cur):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


def _all_kills(stmt: ast.stmt) -> set:
    """Store targets anywhere inside the statement (nested suites included),
    plus framework buffer refreshes: ``x._set_data(...)`` swaps in a fresh
    array for both ``x`` and ``x._data``. Over-approximate on purpose — a
    store in one branch counts, so branch-merging never false-positives."""
    killed = set()
    for node in _walk_shallow(stmt):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                             ast.For, ast.Delete)):
            killed.update(_store_targets(node))
        elif isinstance(node, ast.Call) and call_name(node) == "_set_data" \
                and isinstance(node.func, ast.Attribute):
            killed.add(unparse(node.func.value) + "._data")
            killed.add(unparse(node.func.value))
    return killed


def _check_body(mod: ModuleInfo, qn: str,
                body: List[ast.stmt],
                donors: Dict[str, Tuple[int, ...]]):
    hazards: List[_Hazard] = []
    for stmt in body:
        # 1) reads of expressions donated by a PREVIOUS statement
        if hazards:
            for node in _walk_shallow(stmt):
                if isinstance(node, (ast.Name, ast.Attribute)) \
                        and isinstance(getattr(node, "ctx", None), ast.Load):
                    text = unparse(node)
                    for hz in hazards:
                        if text == hz.expr:
                            yield Finding(
                                "donation-safety", mod.relpath, node.lineno,
                                qn,
                                f"`{hz.expr}` is read after being donated to "
                                f"`{hz.donor}` — donated buffers alias their "
                                "outputs and must not be touched again")
        # 2) kills: any store (incl. tuple-unpack of the donating call's own
        #    results) or x._set_data(...) rebinds the name to a fresh buffer
        killed = _all_kills(stmt)
        if killed:
            hazards = [hz for hz in hazards if hz.expr not in killed]
        # 3) new donations this statement introduces — unless the same
        #    statement immediately rebinds the expression (x = donor(x)),
        #    which is exactly the safe carry-update pattern
        for node in _walk_shallow(stmt):
            if isinstance(node, ast.Call):
                name = call_name(node)
                pos = donors.get(name or "")
                if not pos:
                    continue
                for i in pos:
                    if i < len(node.args) and _is_trackable(node.args[i]):
                        expr = unparse(node.args[i])
                        if expr not in killed:
                            hazards.append(_Hazard(expr, name, node.lineno))
        # sequences fully contained in a nested suite are checked by
        # recursion (step 1's ast.walk covers cross-statement reads)
        for sub in _sub_bodies(stmt):
            yield from _check_body(mod, qn, sub, donors)


def _sub_bodies(stmt: ast.stmt):
    for field in ("body", "orelse", "finalbody"):
        sub = getattr(stmt, field, None)
        if isinstance(sub, list) and sub \
                and not isinstance(stmt, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.ClassDef)):
            yield sub
    for handler in getattr(stmt, "handlers", []) or []:
        yield handler.body


@register_pass(
    "donation-safety",
    "read of an array after it flowed into a donate_argnums position")
def check(mod: ModuleInfo):
    scoped = _collect_donors(mod)
    if not scoped:
        return
    for fn in mod.functions():
        qn = mod.qualname(fn)
        donors = _visible_donors(scoped, qn)
        if donors:
            yield from _check_body(mod, qn, fn.body, donors)
