"""Feedback-fold passes per transaction (the program's count in
``RetrievalMetrics.fold_passes``): one pass per occurrence rank, so a
batch whose busiest user appears k times costs k batch-wide passes."""


def read(ctx):
    per = ctx.counters.get("transactions", 0)
    if not per or "fold_passes" not in ctx.counters:
        return None
    return ctx.counters["fold_passes"] / per
