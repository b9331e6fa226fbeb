"""Host time per round in the benchmark's feed and dispatch spans around
the trainer's calls, in milliseconds."""
from harness import readers

UNIT = "ms"


def read(ctx):
    return readers.host_ms_per_round(ctx)
