"""Device time per round of the attention sub-layer (ops under the model's
``attn`` or ``mla`` scope: its pre-norm, projections, RoPE, softmax), in
every pass, collectives left out, averaged over the chips, in
milliseconds; nothing where the program sets no scopes."""
from harness import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_round(ctx, lambda _phase, attention: attention)
