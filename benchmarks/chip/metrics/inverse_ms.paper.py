"""Device time of the batched ``d x d`` inverses per four-stage epoch:
the scopes ``gram_inverse`` and ``cluster_inverse`` (stage 2) and
``refresh_gram`` (once per call, after the epochs)."""
from benchmarks.chip import scopes

NAMES = ('gram_inverse', 'cluster_inverse', 'refresh_gram')


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    s = scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.inner_s(*NAMES) / per
