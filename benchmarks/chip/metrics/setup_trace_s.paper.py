"""Wall seconds of set-up spent tracing jaxprs and lowering them to MLIR
(nested traces counted once)."""
from benchmarks.chip import scopes


def read(ctx):
    p = scopes.of(ctx).phases
    if p is None:
        return None
    return p['setup']['trace_s']
