"""Share of the traced window in which no op runs on the device, on the
idlest chip, in percent."""
from harness import readers

UNIT = "%"


def read(ctx):
    return readers.idle_percent(ctx)
