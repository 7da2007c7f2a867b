"""Device time under the ``retrieve`` scope per transaction: the pruned
top-K stream (``topk_pruned``), the shortlist merge and the gather of the
shortlist's rows."""
from benchmarks.chip import serve_scopes


def read(ctx):
    per = ctx.counters.get("transactions", 0)
    s = serve_scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.under_s("retrieve") / per
