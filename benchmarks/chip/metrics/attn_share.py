"""Attention's share of the decode step's device time (%), from the trace.

The device self time of the ops of the ``decode_step`` runs in the slice
whose ``op_name`` lies under the model's ``attn`` scope, over that of all
their ops; the op names come from the program's compiled HLO text
(``chiplib/scopes.py``).  None where the program names no ``attn`` scope.
"""
from chiplib import scopes


def read(run):
    return scopes.share(run, "decode_step", "attn")
