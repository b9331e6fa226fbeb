"""Device time per round of the remat policy's recompute (ops under
``checkpoint/rematted_computation``), averaged over the chips, in
milliseconds; nothing where the program sets no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.phase_ms(ctx, "recompute")
