"""FLJ103 — loop-carry stability + int32 counter overflow proof.

The dataplane's bookkeeping (``step``/``n_done``/``sum_steps`` scan
counters, the load generator's Q16.16 ``acc`` arrears register and
``offered``/``injected``/``dropped`` ledgers, ring cursors) is all
int32 by design — the paper's FPGA registers, not bignums.  A fused
window must therefore *prove* its counters cannot wrap within the
declared ``max_steps`` bound, or a long soak run corrupts its own
telemetry in a way no short CI run notices.

The proof is a small abstract interpretation of every ``while``/
``scan`` body in the traced entry, over an **affine-interval domain**:
each value is ``sum_k a_k * X_k + [lo, hi]`` where ``X_k`` are the
loop's carry inputs.  For an integer carry leaf whose output comes
back as ``X_k + [dlo, dhi]`` (a counter: per-step delta in
``[dlo, dhi]``) with a resolvable initial value, the rule checks

    init + max_steps * delta     stays inside the dtype's range.

Output shapes:

* ``X_k + [dlo, dhi]``, delta finite  -> counter; bound checked;
* pure interval within dtype range    -> bounded register (e.g. the
  masked ``acc & 0xFFFF`` arrears) — provably safe;
* ``a * X_k`` with ``a > 1``          -> multiplicative growth —
  finding (overflows for any realistic bound);
* anything else (top / mixed coeffs)  -> not provable either way; the
  rule stays silent rather than guessing (ring payloads, PRNG mixes).

Carry *stability* is checked first: every while/scan carry leaf must
keep its aval between body input and output (jax enforces shape/dtype;
the check also pins weak-type drift, which silently retraces).
"""
from __future__ import annotations

import math

import numpy as np

from scripts.jaxprlint.jaxpr_utils import (as_jaxpr, resolve_const,
                                           walk_eqns)

RULE_ID = "FLJ103"
DESCRIPTION = ("scan/while carries stay stable and int32 counters "
               "provably cannot overflow within the declared max_steps "
               "bound")

INF = math.inf


def _dtype_range(dtype):
    d = np.dtype(dtype)
    if d == np.bool_:
        return (0, 1)
    if d.kind in "iu":
        info = np.iinfo(d)
        return (int(info.min), int(info.max))
    return (-INF, INF)


class AV:
    """Affine-interval value: ``sum coeff[k]*X_k + [lo, hi]``."""
    __slots__ = ("coeff", "lo", "hi")

    def __init__(self, lo, hi, coeff=None):
        self.lo, self.hi = lo, hi
        self.coeff = coeff or {}

    @classmethod
    def top(cls, aval):
        lo, hi = _dtype_range(getattr(aval, "dtype", np.float32))
        return cls(lo, hi)

    @classmethod
    def const(cls, arr):
        arr = np.asarray(arr)
        if arr.size == 0:
            return cls(0, 0)
        if arr.dtype.kind in "iub":
            return cls(int(arr.min()), int(arr.max()))
        return cls(-INF, INF)

    @property
    def pure(self):
        return not self.coeff


def _add(a, b, sign=1):
    coeff = dict(a.coeff)
    for k, v in b.coeff.items():
        coeff[k] = coeff.get(k, 0) + sign * v
        if coeff[k] == 0:
            del coeff[k]
    if sign == 1:
        return AV(a.lo + b.lo, a.hi + b.hi, coeff)
    return AV(a.lo - b.hi, a.hi - b.lo, coeff)


def _mul(a, b):
    for x, y in ((a, b), (b, a)):
        if x.pure and x.lo == x.hi and not math.isinf(x.lo):
            c = x.lo
            coeff = {k: v * c for k, v in y.coeff.items() if v * c != 0}
            lo, hi = sorted((y.lo * c, y.hi * c))
            return AV(lo, hi, coeff)
    if a.pure and b.pure:
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        prods = [p if not math.isnan(p) else INF for p in prods]
        return AV(min(prods), max(prods))
    return AV(-INF, INF)


def _join(vals):
    vals = list(vals)
    coeffs = [frozenset(v.coeff.items()) for v in vals]
    if len(set(coeffs)) == 1:
        return AV(min(v.lo for v in vals), max(v.hi for v in vals),
                  dict(vals[0].coeff))
    if all(v.pure for v in vals):
        return AV(min(v.lo for v in vals), max(v.hi for v in vals))
    return AV(-INF, INF)


def _clamp(v, aval):
    lo, hi = _dtype_range(getattr(aval, "dtype", np.float32))
    if v.pure:
        return AV(max(v.lo, lo), min(v.hi, hi)) if v.lo <= hi \
            and v.hi >= lo else AV(lo, hi)
    return v


def _reduce_count(eqn):
    in_sz = int(np.prod(eqn.invars[0].aval.shape, dtype=np.int64) or 1)
    out_sz = int(np.prod(eqn.outvars[0].aval.shape, dtype=np.int64) or 1)
    return max(in_sz // max(out_sz, 1), 1)


_PASSTHROUGH = {"broadcast_in_dim", "reshape", "squeeze", "copy",
                "stop_gradient", "expand_dims"}
_SHUFFLE = {"transpose", "rev", "slice", "dynamic_slice", "sort",
            "gather"}
_CMP = {"lt", "le", "gt", "ge", "eq", "ne"}


def _eval_eqn(eqn, args, recurse):
    """Abstract-evaluate one eqn; returns a list matching outvars."""
    name = eqn.primitive.name
    out_aval = eqn.outvars[0].aval if eqn.outvars else None
    if name == "add":
        return [_add(args[0], args[1])]
    if name == "sub":
        return [_add(args[0], args[1], sign=-1)]
    if name == "mul":
        return [_mul(args[0], args[1])]
    if name in _PASSTHROUGH:
        a = args[0]
        return [AV(a.lo, a.hi, dict(a.coeff))]
    if name in _SHUFFLE:
        a = args[0]
        if a.pure:
            v = AV(a.lo, a.hi)
            if name == "gather":
                fill = eqn.params.get("fill_value")
                if fill is not None:
                    v = _join([v, AV.const(fill)])
            return [v] * len(eqn.outvars)
        return [AV.top(out_aval)] * len(eqn.outvars)
    if name == "select_n":
        return [_join(args[1:])]
    if name == "convert_element_type":
        a = args[0]
        tgt = eqn.params["new_dtype"]
        if np.dtype(tgt).kind in "iu" and not a.pure:
            return [AV(a.lo, a.hi, dict(a.coeff))]
        return [_clamp(AV(a.lo, a.hi), eqn.outvars[0].aval)]
    if name in _CMP or name == "not":
        return [AV(0, 1)]
    if name in ("reduce_sum", "cumsum"):
        a = args[0]
        if a.pure:
            n = _reduce_count(eqn)
            return [AV(min(a.lo, n * a.lo), max(a.hi, n * a.hi))]
        return [AV.top(out_aval)]
    if name in ("reduce_max", "reduce_min", "reduce_and", "reduce_or",
                "cummax", "cummin"):
        a = args[0]
        return [AV(a.lo, a.hi) if a.pure else AV.top(out_aval)]
    if name in ("argmax", "argmin"):
        n = int(np.prod(eqn.invars[0].aval.shape, dtype=np.int64) or 1)
        return [AV(0, max(n - 1, 0))]
    if name in ("min", "max"):
        a, b = args
        if a.pure and b.pure:
            f = min if name == "min" else max
            return [AV(f(a.lo, b.lo), f(a.hi, b.hi))]
        if a.coeff == b.coeff:
            f = min if name == "min" else max
            return [AV(f(a.lo, b.lo), f(a.hi, b.hi), dict(a.coeff))]
        return [AV.top(out_aval)]
    if name == "clamp":
        lo_op, x, hi_op = args
        if lo_op.pure and hi_op.pure:
            return [AV(lo_op.lo, hi_op.hi)]
        return [AV.top(out_aval)]
    if name == "and":
        a, b = args
        if a.pure and b.pure and a.lo >= 0 and b.lo >= 0:
            return [AV(0, min(a.hi, b.hi))]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name in ("or", "xor"):
        a, b = args
        if a.pure and b.pure and a.lo >= 0 and b.lo >= 0 \
                and a.hi + b.hi < INF:
            bound = (1 << max(int(a.hi).bit_length(),
                              int(b.hi).bit_length())) - 1
            return [AV(0, max(bound, 1))]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name == "shift_right_logical" or name == "shift_right_arithmetic":
        a, s = args
        if a.pure and s.pure and s.lo == s.hi and a.lo >= 0 \
                and not math.isinf(a.hi):
            sh = int(s.lo)
            return [AV(int(a.lo) >> sh, int(a.hi) >> sh)]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name == "shift_left":
        a, s = args
        if a.pure and s.pure and s.lo == s.hi and not math.isinf(a.hi):
            sh = int(s.lo)
            lo, hi = sorted((int(a.lo) << sh, int(a.hi) << sh))
            return [AV(lo, hi)]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name == "rem":
        a, b = args
        if b.pure and b.lo > 0 and not math.isinf(b.hi):
            hi = int(b.hi) - 1
            return [AV(0 if a.pure and a.lo >= 0 else -hi, hi)]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name == "div":
        a = args[0]
        if a.pure and not (math.isinf(a.lo) or math.isinf(a.hi)):
            bound = max(abs(a.lo), abs(a.hi))
            return [AV(-bound, bound)]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name == "neg":
        a = args[0]
        return [AV(-a.hi, -a.lo,
                   {k: -v for k, v in a.coeff.items()})]
    if name == "abs":
        a = args[0]
        if a.pure and not math.isinf(max(abs(a.lo), abs(a.hi))):
            lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
            return [AV(lo, max(abs(a.lo), abs(a.hi)))]
        return [_clamp(AV.top(out_aval), out_aval)]
    if name == "iota":
        n = int(eqn.params.get("shape", (1,))[
            eqn.params.get("dimension", 0)])
        return [AV(0, max(n - 1, 0))]
    if name in ("concatenate", "pad", "dynamic_update_slice"):
        cand = [a for a in args if a.pure]
        if len(cand) == len(args):
            return [_join(args)] * len(eqn.outvars)
        return [AV.top(out_aval)] * len(eqn.outvars)
    if name.startswith("scatter"):
        op, upd = args[0], args[-1]
        if op.pure and upd.pure:
            return [_join([op, upd])]
        return [AV.top(out_aval)]
    if name == "select_and_scatter_add":
        return [AV.top(out_aval)]
    if name == "cond":
        branches = eqn.params.get("branches", ())
        n_out = len(eqn.outvars)
        per_branch = []
        for b in branches:
            per_branch.append(recurse(b, args[1:]))
        if per_branch:
            return [_join([pb[i] for pb in per_branch])
                    for i in range(n_out)]
        return [AV.top(v.aval) for v in eqn.outvars]
    if name in ("jit", "pjit", "custom_jvp_call", "custom_vjp_call",
                "custom_vjp_call_jaxpr", "remat", "checkpoint",
                "closed_call", "core_call", "custom_lin"):
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None and as_jaxpr(sub) is not None:
            try:
                return recurse(sub, args)
            except _Bail:
                pass
        return [AV.top(v.aval) for v in eqn.outvars]
    # unknown primitive (incl. while/scan nested inside the analyzed
    # body, collectives, dot_general, PRNG mixes, float math):
    # conservative dtype-top
    return [_clamp(AV.top(v.aval), v.aval) for v in eqn.outvars]


class _Bail(Exception):
    pass


_MAX_EQNS = 60_000


def _eval_jaxpr(jaxpr, in_avs, budget):
    """Run the abstract interpreter over one (Closed)Jaxpr."""
    j = as_jaxpr(jaxpr)
    env = {}
    consts = getattr(jaxpr, "consts", None)
    if consts is not None:
        for var, val in zip(j.constvars, consts):
            try:
                env[var] = AV.const(val)
            # a const the interval domain can't ingest degrades
            # to dtype-top, never crashes
            except Exception:  # fabriclint: allow(FL007)
                env[var] = AV.top(var.aval)
    else:
        for var in j.constvars:
            env[var] = AV.top(var.aval)
    if len(in_avs) != len(j.invars):
        raise _Bail
    for var, av in zip(j.invars, in_avs):
        env[var] = av

    def read(v):
        if type(v).__name__ == "Literal":
            return AV.const(v.val)
        return env.get(v, AV.top(getattr(v, "aval", None)))

    def recurse(sub, args):
        return _eval_jaxpr(sub, list(args), budget)

    for eqn in j.eqns:
        budget[0] -= 1
        if budget[0] <= 0:
            raise _Bail
        args = [read(v) for v in eqn.invars]
        outs = _eval_eqn(eqn, args, recurse)
        if len(outs) == 1 and len(eqn.outvars) > 1:
            outs = outs * len(eqn.outvars)
        for var, av in zip(eqn.outvars, outs):
            env[var] = av
    return [read(v) for v in j.outvars]


def _loop_sites(jaxpr):
    """Yield (eqn, enclosing_jaxpr) for every while/scan anywhere."""
    from scripts.jaxprlint.jaxpr_utils import param_jaxprs, walk_jaxprs
    for sub in walk_jaxprs(jaxpr):
        j = as_jaxpr(sub)
        for eqn in j.eqns:
            if eqn.primitive.name in ("while", "scan"):
                yield eqn, sub


def _carry_layout(eqn):
    """(body_jaxpr, carry_invars, carry_outvars, init_vars)."""
    if eqn.primitive.name == "while":
        body = eqn.params["body_jaxpr"]
        bn = eqn.params["body_nconsts"]
        cn = eqn.params["cond_nconsts"]
        j = as_jaxpr(body)
        carry_in = j.invars[bn:]
        init = eqn.invars[cn + bn:]
        return body, carry_in, j.outvars, init, bn
    body = eqn.params["jaxpr"]
    nc = eqn.params["num_consts"]
    ncar = eqn.params["num_carry"]
    j = as_jaxpr(body)
    carry_in = j.invars[nc:nc + ncar]
    init = eqn.invars[nc:nc + ncar]
    return body, carry_in, j.outvars[:ncar], init, nc


def _analyze_loop(eqn, enclosing, max_steps):
    """Yield findings for one while/scan eqn."""
    kind = eqn.primitive.name
    body, carry_in, carry_out, init_vars, n_consts = _carry_layout(eqn)
    j = as_jaxpr(body)

    # carry stability: aval must round-trip exactly
    for i, (ci, co) in enumerate(zip(carry_in, carry_out)):
        a, b = ci.aval, getattr(co, "aval", None)
        if b is not None and a != b:
            yield (f"{kind} carry leaf {i} is unstable: body input "
                   f"{a} vs output {b} — jax will weak-type-promote "
                   f"or fail late")

    # seed: carries are affine symbols, everything else dtype-top
    in_avs = []
    for var in j.invars:
        in_avs.append(_clamp(AV.top(var.aval), var.aval))
    for k, var in enumerate(carry_in):
        idx = j.invars.index(var)
        in_avs[idx] = AV(0, 0, {k: 1})
    # const operands with resolvable concrete values tighten the seed
    for pos, var in enumerate(j.invars[:n_consts]):
        cval = resolve_const(eqn.invars[pos], enclosing)
        if cval is not None:
            in_avs[pos] = AV.const(cval)

    budget = [_MAX_EQNS]
    try:
        outs = _eval_jaxpr(body, in_avs, budget)
    except _Bail:
        return
    # abstract interpretation is best-effort: an unmodeled
    # primitive aborts THIS loop's proof rather than killing
    # the whole lint
    except Exception:  # fabriclint: allow(FL007)
        return

    for k, (ci, co_av) in enumerate(
            zip(carry_in, outs[:len(carry_in)] if kind == "scan"
                else outs)):
        aval = ci.aval
        dt = np.dtype(getattr(aval, "dtype", np.float32))
        if dt.kind not in "iu" or len(getattr(aval, "shape", ())) > 1:
            continue
        lo, hi = _dtype_range(dt)
        coeff = co_av.coeff
        if coeff == {k: 1}:
            dlo, dhi = co_av.lo, co_av.hi
            if math.isinf(dhi) or math.isinf(dlo):
                continue       # increment not provable — stay silent
            if dlo >= 0 and dhi == 0:
                continue       # stationary
            init = resolve_const(init_vars[k], enclosing)
            if init is None:
                continue
            init_lo, init_hi = int(init.min()), int(init.max())
            worst_hi = init_hi + max_steps * max(dhi, 0)
            worst_lo = init_lo + max_steps * min(dlo, 0)
            if worst_hi > hi or worst_lo < lo:
                yield (f"{kind} carry leaf {k} ({dt}{list(aval.shape)}) "
                       f"is a counter with per-step delta in "
                       f"[{dlo}, {dhi}] starting at "
                       f"[{init_lo}, {init_hi}]: after the declared "
                       f"max_steps={max_steps} bound it reaches "
                       f"[{worst_lo}, {worst_hi}] — outside the "
                       f"{dt} range [{lo}, {hi}]; widen the counter or "
                       f"lower the window bound")
        elif len(coeff) == 1 and k in coeff and coeff[k] > 1:
            yield (f"{kind} carry leaf {k} ({dt}) grows "
                   f"multiplicatively (out = {coeff[k]}*in + "
                   f"[{co_av.lo}, {co_av.hi}]) — overflows {dt} within "
                   f"~{int(math.log2(max(hi, 2)))} steps regardless of "
                   f"max_steps")


def check(entry, traced, ctx):
    jaxpr = traced.jaxpr
    if jaxpr is None:
        return
    seen = set()
    for eqn, enclosing in _loop_sites(jaxpr):
        key = id(eqn)
        if key in seen:
            continue
        seen.add(key)
        yield from _analyze_loop(eqn, enclosing, entry.max_steps)
