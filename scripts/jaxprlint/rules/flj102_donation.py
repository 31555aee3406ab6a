"""FLJ102 — donation efficacy.

``donate_argnums`` is a *request*: when jax cannot match a donated
input to an output buffer (shape/dtype drift after a refactor, a carry
that stopped being returned), the donation is silently dropped and the
steady-state window quietly doubles its memory traffic — exactly the
kind of rot a perf contract must catch statically.

The check reconciles two independent views of the SAME lowering:

* the traced jaxpr's top-level jit eqns (``jit``; ``pjit`` before
  JAX 0.7) declare which flattened
  inputs are donated (``donated_invars``);
* the lowering marks each really-aliased input: plain jit entries
  carry ``tf.aliasing_output`` arg attributes in StableHLO; shard_map
  entries instead carry ``jax.buffer_donor`` (donation *offered*, the
  match deferred), so for those the rule reconciles against the
  compiled HLO's ``input_output_alias`` header — still host-side
  compilation only, nothing executes.

Every donated invar must show up aliased; a shortfall is a finding.
An entry built with ``expect_donation=True`` that lowers with NO
donated invars at all is also a finding (someone deleted the
``donate_argnums``).
"""
from __future__ import annotations

import re

from scripts.jaxprlint.jaxpr_utils import as_jaxpr

RULE_ID = "FLJ102"
DESCRIPTION = ("every donate_argnums buffer must appear in the lowered "
               "computation's input-output aliasing (dropped donations "
               "double steady-state memory traffic)")

_ALIAS_RE = re.compile(r"tf\.aliasing_output")
_DONOR_RE = re.compile(r"jax\.buffer_donor")
_PAIR_RE = re.compile(r"(?:may|must)-alias")
# the jit primitive's name: ``jit`` in current JAX, ``pjit`` before 0.7
JIT_PRIMITIVES = ("jit", "pjit")


def _donated_count(jaxpr):
    n = 0
    j = as_jaxpr(jaxpr)
    for eqn in j.eqns:
        if eqn.primitive.name in JIT_PRIMITIVES:
            n += sum(bool(d) for d in eqn.params.get("donated_invars",
                                                     ()))
    return n


def check(entry, traced, ctx):
    if not traced.spec.get("expect_donation"):
        return
    jaxpr = traced.jaxpr
    if jaxpr is None:
        return
    n_donated = _donated_count(jaxpr)
    if n_donated == 0:
        yield ("entry declares expect_donation but the traced jaxpr "
               "donates NO buffers — donate_argnums lost on the way to "
               "jit")
        return
    text = traced.lowered_text
    if text is None:
        return
    n_aliased = len(_ALIAS_RE.findall(text))
    if n_aliased >= n_donated:
        return
    n_donor = len(_DONOR_RE.findall(text))
    if n_aliased + n_donor < n_donated:
        missing = n_donated - n_aliased - n_donor
        yield (f"{missing} of {n_donated} donated buffers are missing "
               f"from the lowered input-output aliasing — jax dropped "
               f"those donations silently (output shape/dtype no "
               f"longer matches the donated input)")
        return
    # buffer_donor marks donation OFFERED; whether it matched an
    # output is only visible after compilation
    ctext = traced.compiled_text
    if ctext is None:
        return
    n_pairs = len(_PAIR_RE.findall(ctext))
    if n_pairs < n_donated:
        yield (f"{n_donated - n_pairs} of {n_donated} donated buffers "
               f"were offered (jax.buffer_donor) but the compiled "
               f"input_output_alias table only pairs {n_pairs} — XLA "
               f"could not reuse the rest (output layout/shape no "
               f"longer matches the donated input)")
