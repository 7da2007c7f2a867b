"""Device time of the stage-2 kernels (``graph_prune``, ``cc_hop``) per refresh
that fired inside a transaction."""

KERNELS = ('graph_prune', 'cc_hop')


def read(ctx):
    per = ctx.counters.get('refreshes', 0)
    if not per:
        return None
    t = sum(ctx.reduced.kernel_s.get(k, 0.0) for k in KERNELS)
    if t <= 0:
        return None
    return 1e3 * t / per
