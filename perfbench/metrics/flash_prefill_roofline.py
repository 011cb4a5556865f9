"""The flash prefill kernel's share of its roofline in the traced slice."""
from perfbench import readers, shapes

PATTERN = "flash"


def read(ctx):
    q, context = readers.traced_prefill_context(ctx)
    return readers.roofline_pct(
        ctx, PATTERN, shapes.flash_prefill_flops(ctx["model"], context),
        shapes.flash_prefill_bytes(ctx["model"], q))
