"""Retrieval's share of its roofline: the least time an exact UCB top-K
of every valid request over the whole live catalog needs on this chip,
over the device time of the ``topk`` and ``topk_pruned`` kernels.

Per transaction with ``B`` valid requests, ``N`` live items, width ``d``:
work ``B N 2 (d + d^2)`` flop (the estimate and the quadratic form), bytes
``4 N d + 4 B d^2`` (the catalog once, each request's inverse Gram once).
The least time is the larger of work over the bf16 peak and bytes over
the HBM peak.  Padded rows are not counted, and the work is the same
whatever implements it: a pruned stream that skips tiles does less.
"""


def flops(B, N, d):
    return 2.0 * B * N * (d + d * d)


def bytes_moved(B, N, d):
    return 4.0 * N * d + 4.0 * B * d * d


def least_s(B, N, d, peaks):
    return max(flops(B, N, d) / peaks["bf16_flops_per_s"],
               bytes_moved(B, N, d) / peaks["hbm_bytes_per_s"])


def read(ctx):
    t = sum(ctx.reduced.kernel_s.get(k, 0.0) for k in ("topk", "topk_pruned"))
    if t <= 0:
        return None
    N, d = ctx.cfg["n_items"], ctx.cfg["d"]
    need = sum(least_s(B, N, d, ctx.peaks)
               for B in ctx.counters["valid_per_tx"])
    return 100.0 * need / t
