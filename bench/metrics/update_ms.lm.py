"""Device time per round of the momentum and weight update (ops under the
program's ``local_step`` scope), averaged over the chips, in milliseconds;
nothing where the program sets no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.phase_ms(ctx, "update")
