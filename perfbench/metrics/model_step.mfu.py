"""Operations the model requires for the window's prompt and output tokens
(routed experts only, attention at each token's own context), over the window,
as a share of the chip's bfloat16 peak."""
from perfbench import readers


def read(ctx):
    if ctx["peaks"] is None:
        return None
    flops = readers.required_flops(ctx)
    if flops <= 0.0:
        return None
    return 100.0 * flops / ctx["seconds"] / ctx["peaks"]["bf16_flops_per_s"]
