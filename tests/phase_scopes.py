"""The phase scope of each op of a compiled fused step (shared by
``test_spans.py`` and the multi-device ``distributed_cases.py``).

The step is compiled, not only lowered: XLA inlines called computations and
prefixes the op_names of loop bodies with their caller's, as a profiler
trace of the chip shows them."""
from __future__ import annotations

import re

# every named scope the fused step opens (core/summa3d.py, local_spgemm.py)
PHASES = frozenset({
    "spgemm.select", "spgemm.gather", "spgemm.esc.sort_a",
    "spgemm.esc.expand", "spgemm.esc.compress", "spgemm.hash",
    "spgemm.split", "spgemm.fiber_exchange", "spgemm.merge",
})
# the ops that carry the step's time on the chip
CHECKED = frozenset({"gather", "scatter", "sort", "reduce"})

_OP = re.compile(r"\s*(?:ROOT )?%?\S+ = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_text(lowered) -> str:
    """The compiled module as HLO text, with each op's metadata."""
    return lowered.compile().as_text()


def scopes(op_name: str) -> list:
    return [seg for seg in op_name.split("/") if seg.startswith("spgemm.")]


def checked_ops(hlo: str) -> list:
    """(opcode, op_name) of every gather, scatter, sort and reduce op."""
    out = []
    for line in hlo.splitlines():
        op = _OP.match(line)
        if op and op.group(1) in CHECKED:
            name = _OP_NAME.search(line)
            out.append((op.group(1), name.group(1) if name else ""))
    return out


def outside_one_phase(hlo: str) -> list:
    """The checked ops whose op_name does not lie under exactly one phase
    scope."""
    return [(opcode, name) for opcode, name in checked_ops(hlo)
            if len(scopes(name)) != 1 or scopes(name)[0] not in PHASES]
