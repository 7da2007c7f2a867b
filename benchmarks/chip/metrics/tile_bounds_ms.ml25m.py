"""Device time under the ``tile_bounds`` scope per transaction: every
request's UCB upper bound on every catalog tile (``eigvalsh`` of each
request's inverse Gram, the centroid and max-norm terms)."""
from benchmarks.chip import serve_scopes


def read(ctx):
    per = ctx.counters.get("transactions", 0)
    s = serve_scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.under_s("tile_bounds") / per
