"""Device time under the ``refresh`` scope per refresh that fired inside
a transaction: the whole stage-2 refresh (prune, components, the batched
inverses, the cluster reductions and snapshots), not only the graph
kernels ``refresh_ms.serve`` reads."""
from benchmarks.chip import serve_scopes


def read(ctx):
    per = ctx.counters.get("refreshes", 0)
    s = serve_scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.under_s("refresh") / per
