"""The expert layer's ops in a compiled decode step.

The program's held experts are grouped matmuls (``jax.lax.ragged_dot``)
under the ``moe/moe_experts`` scope.  XLA's TPU expansion of a ragged dot
names the ops it makes ``ragged-dot-*`` and drops the scopes from their
``op_name``; the program has no other ragged dot, so those ops are put back
under ``moe/moe_experts``.  On the CPU, and in a program without ragged
dots, the names are left as they are.
"""
from __future__ import annotations

from chiplib import scopes

EXPERTS = "moe/moe_experts"


def expert_names(names: dict) -> dict:
    """``names`` (instruction name -> op_name) with the ragged dots' ops
    under ``moe/moe_experts``."""
    return {k: f"{EXPERTS}/{v}" if v.startswith("ragged-dot") else v for k, v in names.items()}


def decode_names(ctx) -> dict:
    """Instruction name -> op_name of the cell's ``decode_step``, the
    ragged dots' ops under ``moe/moe_experts``."""
    return expert_names(scopes.program_names(ctx, "decode_step"))
