"""Device time under the ``gather_score`` scope per transaction: the
gather of each request's ``Minv``/``uMcinv`` rows, the user vectors and
the beta mix."""
from benchmarks.chip import serve_scopes


def read(ctx):
    per = ctx.counters.get("transactions", 0)
    s = serve_scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.under_s("gather_score") / per
