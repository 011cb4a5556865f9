"""The HTTP edge's self time, the median: its span (headers and body in, to the
reply's last byte written) less the handler's span inside it."""
from perfbench import timeline


def read(ctx):
    return timeline.flight_median_ms(ctx, "edge_self_s")
