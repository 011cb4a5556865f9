"""Time per output token after the first, the median: the flight recorder's
``tpot_s`` (last less first token, over the tokens between)."""
from perfbench import timeline


def read(ctx):
    return timeline.flight_median_ms(ctx, "tpot_s")
