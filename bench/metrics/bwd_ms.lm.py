"""Device time per round of the backward pass (ops whose name stack holds
``transpose(``), averaged over the chips, in milliseconds; nothing where
the program sets no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.phase_ms(ctx, "bwd")
