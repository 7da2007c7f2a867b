"""Busy device time under no named scope per four-stage epoch: what the
scopes leave uncovered (copies XLA inserts, loops it leaves without an
``op_name``)."""
from benchmarks.chip import scopes


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    s = scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.scope_s.get('', 0.0) / per
