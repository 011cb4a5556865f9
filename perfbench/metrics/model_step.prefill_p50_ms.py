"""The admission's own prefill, the median: its dispatch to the first token on
the host."""
from perfbench import timeline


def read(ctx):
    return timeline.flight_median_ms(ctx, "prefill_s")
