"""The part of the collective-permutes' time during which no other op runs
on that chip, per round, averaged over the chips, in milliseconds; nothing
where no op is a collective."""
from harness import readers

UNIT = "ms"


def read(ctx):
    return readers.collective_ms_per_round(ctx, exposed=True)
