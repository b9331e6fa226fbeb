"""Device time per round of the clones that XLA's rematerialization pass
made to fit memory (op names ending ``.remat<n>``), averaged over the
chips, in milliseconds; nothing where the program sets no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.phase_ms(ctx, "xla_remat")
