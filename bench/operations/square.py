"""C = A·A: the operand squared (HipMCL's expansion, a graph's two-hop
paths), one ``batched_summa3d`` call per use.

An operation module gives the harness the host operands of the product
(``operands``: the left and the right ``Operand``, from which the harness
scatters A and B and builds the reference and the counts) and the system
under test (``program``: ``multiply(consumer, exec_spec=None)`` making one
call that hands each batch to ``consumer``). A new operation is a new file
in ``bench/operations/``, named by a traffic mix's ``"operation"``.
"""
from __future__ import annotations


def operands(op):
    return op, op


def program(A, B, grid, budget, spec):
    from repro.core.batched import batched_summa3d

    def multiply(consumer, exec_spec=None):
        return batched_summa3d(A, B, grid, budget, consumer=consumer,
                               spec=spec, exec_spec=exec_spec)

    return multiply
