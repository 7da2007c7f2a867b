"""Device time of the batched ``d x d`` inverses per refresh that fired
inside a transaction: stage 2's scopes ``gram_inverse`` and
``cluster_inverse`` under ``refresh`` (``kernels/spdinv`` and the
gathers and layout work around it)."""
from benchmarks.chip import serve_scopes

NAMES = ("gram_inverse", "cluster_inverse")


def read(ctx):
    per = ctx.counters.get("refreshes", 0)
    s = serve_scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.inner_s(*NAMES) / per
