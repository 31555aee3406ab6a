"""Shared AST helpers for fabriclint rules."""
from __future__ import annotations

import ast


def dotted_name(node):
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call):
    """Dotted name of a call's callee, else None."""
    return dotted_name(call.func)


def identifiers_in(node):
    """Every identifier-ish token in a subtree: Name ids, Attribute
    attrs, and string dict keys used as subscripts (``done["flags"]``)."""
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
        elif isinstance(n, ast.Subscript):
            sl = n.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                out.add(sl.value)
    return out


def import_aliases(tree):
    """Map local alias -> imported module/symbol dotted path.

    ``import numpy as np`` -> {"np": "numpy"};
    ``from jax import random`` -> {"random": "jax.random"};
    ``from time import time`` -> {"time": "time.time"}.
    """
    aliases = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def resolve_call(call: ast.Call, aliases):
    """Fully-resolved dotted callee using the module's import aliases.

    ``np.random.default_rng(...)`` -> "numpy.random.default_rng".
    """
    name = call_name(call)
    if name is None:
        return None
    head, _, rest = name.partition(".")
    base = aliases.get(head, head)
    return f"{base}.{rest}" if rest else base


def func_args_of_call(call: ast.Call):
    """Positional args + keyword values of a call (for finding
    function-valued arguments like scan bodies)."""
    return list(call.args) + [k.value for k in call.keywords]


TRACER_ROOTS = {
    "jax.lax.scan", "lax.scan",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch",
    "jax.jit", "jit",
    "shard_map", "jax.shard_map", "transport.shard_map",
}


def traced_function_defs(tree):
    """FunctionDef/Lambda nodes passed (by name or inline) to a tracing
    primitive — scan/while/fori/cond/switch bodies, jitted or
    shard_mapped functions.  These run under trace: host syncs and host
    entropy inside them are real bugs, not style."""
    # local defs by name, per enclosing scope walk (name collisions across
    # scopes are acceptable for a lint: we over-approximate)
    defs = {}
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(n.name, n)
    traced = []
    seen = set()
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        name = call_name(n)
        if name not in TRACER_ROOTS:
            continue
        for arg in func_args_of_call(n):
            target = None
            if isinstance(arg, ast.Lambda):
                target = arg
            elif isinstance(arg, ast.Name) and arg.id in defs:
                target = defs[arg.id]
            if target is not None and id(target) not in seen:
                seen.add(id(target))
                traced.append(target)
    return traced
