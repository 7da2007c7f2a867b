"""Device time of the simulated users per four-stage epoch: the scopes
``env_contexts`` (candidate draws) and ``env_rewards`` (click draws)."""
from benchmarks.chip import scopes

NAMES = ('env_contexts', 'env_rewards')


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    s = scopes.of(ctx)
    if not per or not s.named:
        return None
    return 1e3 * s.inner_s(*NAMES) / per
