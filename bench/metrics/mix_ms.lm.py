"""Device time per round of the gossip's on-chip work (ops under the
program's ``gossip`` scope that are not collectives: wire casts, the mix),
averaged over the chips, in milliseconds; nothing where the program sets
no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.phase_ms(ctx, "mix")
