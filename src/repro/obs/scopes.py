"""Which named scope each instruction of a compiled program belongs to.

Every function block the models call (``paged_attention``, ``attention``,
``rmsnorm``, ``ssd_scan``) runs under ``jax.named_scope(<block>)``
(:meth:`repro.core.blocks.FunctionBlockRegistry.call`), whichever target
is bound; the paged KV writes run under ``kv_write``, the LM head under
``head``, the MLP under ``mlp``, the expert layer under ``moe`` (its
router under ``router``, its expert products under ``experts``) and
sampling under ``sample``.  XLA keeps
the scope path in each instruction's ``op_name`` metadata, through fusion
and inside while bodies.  A profiler trace captured without HLO protos
names only the instruction (``fusion.3``, ``while.42``), so
:func:`op_scopes` reads the map from the program's compiled text
(``jitted.lower(...).compile().as_text()``).
"""

from __future__ import annotations

import re
from typing import Iterable

__all__ = ["OTHER", "module_name", "op_scopes"]

#: the scope of an instruction that none of the names covers
OTHER = "other"

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def module_name(hlo_text: str) -> str:
    """The ``HloModule`` name of a compiled program's text
    (``jit_decode_fn``): the name its runs carry in a profiler trace."""
    m = _MODULE.search(hlo_text)
    if m is None:
        raise ValueError("no HloModule header in the text")
    return m.group(1)


def op_scopes(hlo_text: str, names: Iterable[str]) -> dict[str, str]:
    """Map each instruction of the module, in every computation, to the
    innermost of ``names`` on its ``op_name`` path, or to :data:`OTHER`.

    A path component matches a name exactly (``jit(f)/while/body/
    paged_attention/while`` lies in ``paged_attention``); the component
    nearest the instruction wins when scopes nest (``head/rmsnorm`` lies
    in ``rmsnorm``).
    """
    wanted = set(names)
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        scope = OTHER
        meta = _OP_NAME.search(line)
        if meta is not None:
            for part in reversed(meta.group(1).split("/")):
                if part in wanted:
                    scope = part
                    break
        out[m.group(1)] = scope
    return out
