"""Model FLOPs of the traced window's tokens (3 × forward, no recompute)
over the window and the chips' bf16 peak, in percent."""
from harness import readers

UNIT = "%"


def read(ctx):
    return readers.mfu_percent(ctx)
