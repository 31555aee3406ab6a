"""Attribute the device time of a traced window to the program's scopes.

The program names each phase of its fused step with a
``jax.named_scope`` (``repro.core.telemetry.SCOPE_*``, all starting
``dagger.``), and the compiled program keeps the path of scopes each
instruction ran under in its ``op_name`` metadata::

    jit(run_steps)/while/body/vmap(dagger.client)/dagger.nic.fetch/...

The TPU trace names a device operation by its instruction
(``fusion.592 s32[1048576,16]``, ``bench.trace.op_name``), so the
compiled text of the window program maps every traced operation of that
program to its scope path.  The text comes from the engine's own entry
(``TenantEngine.lower_steps`` on the rig's state after the traced
windows): the same arguments give the same program, which JAX finds in
its caches, so nothing is built twice.  A program without that entry
or without scopes yields nothing, and every reader of a scope returns
``None`` there.

``attribute`` splits the busy time of a chip exactly (the union of its
operations' intervals inside the traced span, as ``Reduced.busy_ns``):

* self time per scope path (``dagger.client/dagger.nic.fetch/
  dagger.ring.peek``): each nanosecond of busy time goes to the
  operation that first covered it, and so to that operation's path;
* ``unscoped``: operations of the window program under no ``dagger.``
  scope, and operations of other programs (the host's sample copies);
* inclusive time of a scope: self time of every path that holds it.

An instruction without metadata of its own (a copy XLA inserted between
a scatter and a gather, say) takes the path of its first operand that
has one, following operands, and failing that the path of its first
user that has one, following users (a copy of a loop-carried ring into
the layout its reader needs); ``Attribution.from_operand`` and
``.from_user`` count the operations so placed.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

UNSCOPED = "unscoped"
SCOPE = re.compile(r"dagger\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+) = (?P<rest>.*)$")
OP_NAME = re.compile(r'op_name="(?P<path>[^"]*)"')
OPERAND = re.compile(r"%(?P<name>[^\s,(){}=]+)")
OPERAND_DEPTH = 8


def path_of(op_name: str) -> tuple:
    """The ``dagger.`` scopes of an ``op_name``, outermost first
    (``vmap(dagger.client)`` counts as ``dagger.client``).  Where XLA
    merged instructions it joins their names with ``;``: the first
    name counts."""
    return tuple(SCOPE.findall(op_name.split(";")[0]))


@dataclass
class ScopeMap:
    """Scope paths of a compiled program's instructions."""
    paths: dict                        # instruction name -> scope path
    from_operand: frozenset = frozenset()  # placed by an operand's path
    from_user: frozenset = frozenset()     # placed by a user's path

    def scoped(self) -> bool:
        return any(self.paths.values())


def scope_map(hlo_text: str) -> ScopeMap:
    """The scope map of a compiled HLO module's text.  An instruction
    with no ``op_name`` of its own takes the path of its operands (the
    first that has one, following operands), and failing that of its
    users: a layout copy of a loop-carried ring has only the loop's
    parameter above it, and exists for the operation that reads it."""
    own, operands, users = {}, {}, {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.group("name"), m.group("rest")
        ops = OPERAND.findall(rest.split(", metadata=")[0])
        for op in ops:
            users.setdefault(op, []).append(name)
        meta = OP_NAME.search(rest)
        if meta:
            own[name] = path_of(meta.group("path"))
        else:
            operands[name] = ops

    def follow(name, edges):
        """The path of the nearest instruction with an ``op_name`` along
        ``edges`` (breadth first, at most ``OPERAND_DEPTH`` hops)."""
        level, seen = [name], {name}
        for _ in range(OPERAND_DEPTH):
            nxt = []
            for n in level:
                for m in edges.get(n, ()):
                    if m in own:
                        if own[m]:
                            return own[m]
                    elif m not in seen:
                        seen.add(m)
                        nxt.append(m)
            level = nxt
        return ()

    paths = dict(own)
    up, down = set(), set()
    for name in operands:
        paths[name] = follow(name, operands)
        if paths[name]:
            up.add(name)
            continue
        paths[name] = follow(name, users)
        if paths[name]:
            down.add(name)
    return ScopeMap(paths, frozenset(up), frozenset(down))


def window_scope_map(rig):
    """The scope map of the rig's window program, from the engine's own
    entry; ``None`` where the engine has no ``lower_steps``."""
    lower = getattr(rig.engine, "lower_steps", None)
    if lower is None:
        return None
    lowered = lower(rig.cst, rig.sst, rig.k, hstate=rig.hstate,
                    tel=rig.tel, gen=rig.gst)
    return scope_map(lowered.compile().as_text())


@dataclass
class Attribution:
    """One chip's busy time inside the traced span, split by scope."""
    busy_ns: int
    self_ns: dict = field(default_factory=dict)   # path key -> ns
    from_operand: int = 0    # operations placed by an operand's path
    from_user: int = 0       # operations placed by a user's path
    missing: int = 0         # window-program operations not in the map

    def inclusive_ns(self, scope: str) -> int:
        """Self time of every path holding ``scope``; a name ending in
        ``.`` matches every scope it begins (``dagger.nic.``)."""
        def holds(key):
            parts = key.split("/")
            if scope.endswith("."):
                return any(p.startswith(scope) for p in parts)
            return scope in parts
        return sum(ns for key, ns in self.self_ns.items()
                   if key != UNSCOPED and holds(key))

    def scoped_share(self) -> float:
        """Share of busy time under some ``dagger.`` scope."""
        if not self.busy_ns:
            return 0.0
        return 1.0 - self.self_ns.get(UNSCOPED, 0) / self.busy_ns

    def top(self, n: int = 12) -> list:
        """[path, seconds] of the ``n`` largest self times, ``unscoped``
        among them where it is that large."""
        return [[k, ns * 1e-9] for k, ns in sorted(
            self.self_ns.items(), key=lambda kv: -kv[1])[:n]]


def attribute(trace, smap: ScopeMap, program: str, chip: int = 0):
    """Split chip ``chip``'s busy time in ``trace`` (a ``bench.trace
    .Reduced``) by the scope paths of ``smap``, the map of the program
    whose runs match ``program``.  Self times sum exactly to
    ``trace.busy_ns(chip)``."""
    runs = trace.module_runs(chip, program)
    att = Attribution(busy_ns=0)
    ops = sorted((max(s, trace.t0), min(e, trace.t1), n)
                 for n, s, e in trace.chips[chip].ops
                 if e > trace.t0 and s < trace.t1)
    cur = trace.t0
    for s, e, name in ops:
        new = e - max(s, cur)
        cur = max(cur, e)
        if new <= 0:
            continue
        att.busy_ns += new
        instr = name.split(" ")[0]
        path = ()
        if any(rs <= s < re_ for rs, re_ in runs):
            if instr in smap.paths:
                path = smap.paths[instr]
                att.from_operand += instr in smap.from_operand
                att.from_user += instr in smap.from_user
            else:
                att.missing += 1
        key = "/".join(path) if path else UNSCOPED
        att.self_ns[key] = att.self_ns.get(key, 0) + new
    return att


def of(ctx):
    """The attribution of a harness trace context's chip 0, made once per
    context and kept in it under ``"scopes"``; ``None`` where the program
    names no ``dagger.`` scope."""
    if "scopes" not in ctx:
        smap = window_scope_map(ctx["rig"])
        att = None
        if smap is not None and smap.scoped():
            att = attribute(ctx["trace"], smap, ctx["rig"].window_program)
        ctx["scopes"] = att
    return ctx["scopes"]


def per_step_us(ctx, scope: str):
    """Inclusive device time under ``scope`` per fused step of the traced
    windows (us); ``None`` where the program has no such scope or the
    windows ran no step."""
    att = of(ctx)
    if att is None or not ctx["steps"]:
        return None
    ns = att.inclusive_ns(scope)
    if not ns:
        return None
    return ns * 1e-3 / ctx["steps"]
