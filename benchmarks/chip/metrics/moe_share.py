"""The expert layer's share of the decode step's device time (%), from the
trace: the device self time of the ``decode_step`` ops under the model's
``moe`` scope (router, sort and combine, the held experts and the shared
expert), over that of all its ops (``chiplib/scopes.py``; the held
experts' ragged dots put back in that scope by ``chiplib/moe_scopes.py``).
None where the program names no ``moe`` scope."""
from chiplib import moe_scopes, scopes

SCOPE = "moe"


def read(run):
    tr = run.trace
    if tr is None or not tr.module_runs("decode_step"):
        return None
    names = moe_scopes.decode_names(run.ctx)
    if not any(scopes.scope_of(n, (SCOPE,)) == SCOPE for n in names.values()):
        return None
    by = scopes.self_time_by_scope(tr, "decode_step", names)
    total = sum(by.values())
    return 100.0 * by.get(SCOPE, 0.0) / total if total else None
