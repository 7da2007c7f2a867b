"""Busy device time under no named scope per transaction: what the serve
scopes leave uncovered (copies XLA inserts, ops outside the
transaction)."""
from benchmarks.chip import serve_scopes


def read(ctx):
    per = ctx.counters.get("transactions", 0)
    s = serve_scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.scope_s.get("", 0.0) / per
