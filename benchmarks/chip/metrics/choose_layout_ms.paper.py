"""Device time of the ``choose`` scope outside the ``choose`` kernel per
four-stage epoch: padding the contexts, the layout change and the
chosen-x gather around the kernel ``choose_ms.paper`` reads."""
from benchmarks.chip import scopes


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    s = scopes.of(ctx)
    if not per or not s.named:
        return None
    t = s.inner_s('choose') - ctx.reduced.kernel_s.get('choose', 0.0)
    return 1e3 * max(t, 0.0) / per
