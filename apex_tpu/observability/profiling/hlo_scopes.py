"""Which part of the train step a compiled instruction belongs to.

A device trace names operations by their HLO instruction (``fusion.323``,
``copy.170``), which says nothing about where in the step they run. The
``jax.named_scope`` names the program's modules leave in each instruction's
``op_name`` metadata do: :func:`op_scopes` reads them out of
``compiled.as_text()``.

On jax 0.9 an ``op_name`` is a path of transforms, structure and scopes
ending in the primitive, the scope sitting either beside a transform or
inside its brackets::

    jit(step)/jvp()/while/body/closed_call/gpt2/attn/dot_general
    jit(step)/jvp(gpt2/final_ln)/mul
    jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/gpt2/mlp/mul
    jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/gpt2/attn/add
    jit(step)/amp/update/cond/branch_1_fun/fused_adam/tree/mul

The phase is read off the transforms (``rematted_computation`` is the
backward pass recomputing the forward, ``transpose(`` the backward proper,
``jvp(`` the forward), or off the scope where there is no transform (the
optimizer's families); the scope is what is left of the path once
transforms, structure and the primitive are taken away.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Tuple

__all__ = ["PHASES", "op_scopes", "phase_and_scope"]

PHASES = ("forward", "recompute", "backward", "optimizer", "other")

#: scope families that run after the backward pass, outside any transform
#: (``optimizer/`` is for a caller's own scope round ``apply_updates``)
_OPTIMIZER_SCOPES = ("fused_adam/", "fused_lamb/", "amp/unscale",
                     "amp/update", "optimizer/")

_JIT = re.compile(r"jit\([^()]*\)")
_STRUCTURE = re.compile(
    r"^(while|body|cond|closed_call|checkpoint|rematted_computation|"
    r"custom_[jv][jv]p_call\w*|shard_map|pallas_call|core_call|scan|"
    r"branch_\d+_fun|remat\d*|jvp|transpose|vmap)?$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def phase_and_scope(op_name: str) -> Tuple[str, str]:
    """``(phase, scope)`` of one ``op_name`` metadata string."""
    parts = _JIT.sub("", op_name).replace("(", "/").replace(")", "/")
    parts = [p for p in parts.split("/")[:-1]
             if not _STRUCTURE.match(p) and "->" not in p]
    scope = "/".join(parts)
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    elif scope.startswith(_OPTIMIZER_SCOPES):
        phase = "optimizer"
    else:
        phase = "other"
    return phase, scope


def op_scopes(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """``{instruction: (phase, scope)}`` for every instruction of a compiled
    module's text (instruction names are unique in a module). A fusion is
    booked to what most of its fused computation's instructions say, since
    its own metadata is that of one of them; an instruction with no
    metadata reads ``("other", "")``."""
    own: Dict[str, Tuple[str, str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, list] = collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        instr = _INSTRUCTION.match(line)
        if instr is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                computation = head.group(1)
            continue
        name = instr.group(1)
        meta = _OP_NAME.search(line)
        own[name] = phase_and_scope(meta.group(1)) if meta else ("other", "")
        members[computation].append(name)
        called = _CALLS.search(line)
        if called is not None:
            calls[name] = called.group(1)
    out = dict(own)
    for name, computation in calls.items():
        votes = collections.Counter(
            own[m] for m in members.get(computation, ())
            if own[m][0] != "other")
        if votes:
            out[name] = votes.most_common(1)[0][0]
    return out
