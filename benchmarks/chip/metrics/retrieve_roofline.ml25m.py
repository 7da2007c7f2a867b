"""The pruned top-K kernel's share of its roofline, over the (user block,
tile) visits it made: the least time those visits need on this chip over
the device time of the ``topk_pruned`` kernel.

A visit scores ``Bu`` requests against a tile of ``Bt`` items of width
``d``: work ``Bu Bt 2 (d + d^2)`` flop (the estimate and the quadratic
form), bytes ``4 Bt d`` (the tile's rows).  Each user block also reads
its requests' inverse Grams once per transaction, ``4 Bu d^2`` bytes.
The least time is the larger of the work over the bf16 peak and the
bytes over the HBM peak.  Skipped visits are not counted: they do no
work, and a skip is read from a bound already in VMEM.
"""


def flops(visits, Bu, Bt, d):
    return 2.0 * visits * Bu * Bt * (d + d * d)


def bytes_moved(visits, blocks, Bu, Bt, d):
    return 4.0 * visits * Bt * d + 4.0 * blocks * Bu * d * d


def least_s(c, peaks):
    visits = c["tiles_total"] - c["tiles_skipped"]
    blocks = c["transactions"] * c["user_blocks_per_tx"]
    Bu, Bt, d = c["block_users"], c["tile_items"], c["d"]
    return max(flops(visits, Bu, Bt, d) / peaks["bf16_flops_per_s"],
               bytes_moved(visits, blocks, Bu, Bt, d)
               / peaks["hbm_bytes_per_s"])


def read(ctx):
    t = ctx.reduced.kernel_s.get("topk_pruned", 0.0)
    if t <= 0 or "tiles_total" not in ctx.counters:
        return None
    return 100.0 * least_s(ctx.counters, ctx.peaks) / t
