"""The optimizer's share of the train step's device time (%), from the trace.

The device self time of the ops of the ``train_step`` runs in the slice
whose ``op_name`` lies under the train step's ``optimizer`` scope (the
AdamW update), over that of all their ops; the op names come from the
program's compiled HLO text (``chiplib/scopes.py``).  None where the
program names no ``optimizer`` scope.
"""
from chiplib import scopes


def read(run):
    return scopes.share(run, "train_step", "optimizer")
