"""Backend compiles (each a compile or a persistent-cache read) that JAX
made while the timed window ran."""
from benchmarks.chip import scopes


def read(ctx):
    p = scopes.of(ctx).phases
    if p is None:
        return None
    return p['window']['compiles']
