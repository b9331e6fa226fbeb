"""Device time of the gossip's collective-permute ops per round, averaged
over the chips, in milliseconds; nothing where no op is a collective."""
from harness import readers

UNIT = "ms"


def read(ctx):
    return readers.collective_ms_per_round(ctx, exposed=False)
