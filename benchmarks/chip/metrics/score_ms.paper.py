"""Device time of the ``score`` scope per four-stage epoch: user vectors,
the beta gate and the mix of own and cluster statistics, every round."""
from benchmarks.chip import scopes


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    s = scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.inner_s('score') / per
