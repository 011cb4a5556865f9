"""The held experts' grouped products (XLA's ragged-dot kernel) in the traced
slice: the least time the chip could take for the pairs that landed here (the
larger of operations over peak and bytes over bandwidth, an expert's weights
counted once a call if any token reached it) over the kernel's device time."""
from perfbench import archs, readers

PATTERN = "ragged-dot"


def read(ctx):
    arch = archs.of(ctx["model"])
    routed = ctx["counters"].get("engine.moe_assignments")
    held = ctx["counters"].get("engine.moe_assignments_held")
    if not hasattr(arch, "moe_grouped_flops") or not routed or not held:
        return None
    m = ctx["model"]
    work = arch.traced_work(ctx)
    layers = m.count("E")
    tokens = work["prefill_tokens"] + work["decode_tokens"]
    pairs = tokens * layers * m.experts_per_tok * held / routed
    steps = int(round(work["decode_steps"]))
    calls = list(work["admissions"]) + [work["decode_tokens"] / steps] * steps
    return readers.roofline_pct(
        ctx, PATTERN, arch.moe_grouped_flops(m, pairs),
        layers * arch.moe_grouped_bytes(m, pairs / layers, calls))
