"""Wait for a slot, the median: ``batcher.submit`` to the admission's dispatch."""
from perfbench import timeline


def read(ctx):
    return timeline.flight_median_ms(ctx, "batcher_wait_s")
