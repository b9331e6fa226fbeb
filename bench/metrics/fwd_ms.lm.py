"""Device time per round of the forward pass: ops under the program's
``grad`` scope (or an attention scope outside it: the mask and RoPE tables
that tracing hoisted out) that are neither backward nor recompute,
averaged over the chips, in milliseconds; nothing where the program sets
no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.phase_ms(ctx, "fwd")
