"""Device busy time per round less the collective-permutes: the local
scan's forward, backward and update, in milliseconds, averaged over the
chips."""
from harness import readers

UNIT = "ms"


def read(ctx):
    return readers.compute_ms_per_round(ctx)
