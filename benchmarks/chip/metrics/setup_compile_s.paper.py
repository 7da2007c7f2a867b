"""Wall seconds of set-up spent in backend compiles, a persistent-cache
read included."""
from benchmarks.chip import scopes


def read(ctx):
    p = scopes.of(ctx).phases
    if p is None:
        return None
    return p['setup']['compile_s']
