"""Device time under the ``stage2`` scope per four-stage epoch: the whole
refresh (prune, components, the batched inverses, the cluster
reductions), not only the graph kernels ``stage2_ms.paper`` reads."""
from benchmarks.chip import scopes


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    s = scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.under_s('stage2') / per
