"""Device time of the ``rank1_update_inv`` kernel per four-stage epoch."""

KERNELS = ('rank1_update_inv',)


def read(ctx):
    per = ctx.counters.get('epochs', 0)
    if not per:
        return None
    t = sum(ctx.reduced.kernel_s.get(k, 0.0) for k in KERNELS)
    if t <= 0:
        return None
    return 1e3 * t / per
