"""Model FLOPs of the traced window's tokens (3 × forward, no recompute;
``harness/flops_hybrid.py``) over the window and the chips' bf16 peak, in
percent: ``mfu.lm`` for the Mamba-2 / attention hybrid."""
from harness import flops_hybrid, readers

UNIT = "%"


def read(ctx):
    cell = ctx["cell"]
    per_item = flops_hybrid.train_flops_per_item(
        cell["config_spec"], cell["traffic_spec"]["data"])
    chips = len(ctx["trace"]["devices"])
    return 100.0 * per_item * ctx["items"] / readers.window_s(ctx) / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
