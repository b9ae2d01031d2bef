"""The program's named scopes, and which compiled instruction carries which.

The step programs name their layers with ``jax.named_scope``; XLA keeps the
name stack in every instruction's ``op_name`` metadata, through fusion, so
a profiler trace, whose device ops are named by instruction, can be summed
by layer. Transforms wrap a scope's name (``vmap(jvp(head))``,
``transpose(jvp(lstmp))``), so one name covers the forward and the
backward pass of its layer. An instruction that XLA adds and that
carries no op metadata (a layout copy, an async copy's start and done)
moves a value that the program made: it takes the scope of that value,
its first operand.

  embed            the embedding gather (``models/lstm.py``)
  lstmp            each LSTMP layer's ``lax.scan`` and its residual
  head             the softmax head's matmul and bias
  xent             the cross-entropy (``models/model.py``)
  adaalter_update  the per-step optimizer update, per leaf or flat
  sync             the sync round (``opt.sync``, the flat plane's sync)
  ef_encode        the error-feedback encode of the sync payload
  worker_mean      the mean over workers, the round's collective
"""
from __future__ import annotations

import re
from typing import Dict, Optional

SCOPES = ("embed", "lstmp", "head", "xent", "adaalter_update", "sync",
          "ef_encode", "worker_mean")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_FIRST_OPERAND = re.compile(r"[\w-]+\(%([^\s,)]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_NOT_A_SCOPE = ("jit", "pjit")     # ``jit(f)`` names a program, not a scope


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` in one ``op_name``, or None."""
    found = None
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if m is None or m.group(1) in _NOT_A_SCOPE:
                break
            part = m.group(2)
        if part in SCOPES:
            found = part
    return found


def op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction: scope}`` for every instruction of a compiled
    program's text (``compiled.as_text()``); an instruction outside every
    scope maps to None. The text lists an instruction's operands before
    it, so one pass finds the scope a metadata-less one takes."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name = _OP_NAME.search(line)
        if name is not None:
            out[m.group(1)] = scope_of(name.group(1))
        else:
            operand = _FIRST_OPERAND.search(line, m.end())
            out[m.group(1)] = out.get(operand.group(1)) if operand else None
    return out
