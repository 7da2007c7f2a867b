"""Busy device time outside every named Pallas kernel, per four-stage epoch: the
XLA glue around the kernels (gathers, the shortlist merge, tile bounds,
batched inverses, sampling)."""


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    if not per:
        return None
    named = sum(ctx.reduced.kernel_s.values())
    return 1e3 * max(ctx.reduced.busy_s - named, 0.0) / per
