"""The handler's self time, the median: its span less the batcher's (submit to
the request's future resolved) inside it."""
from perfbench import timeline


def read(ctx):
    return timeline.flight_median_ms(ctx, "handler_self_s")
