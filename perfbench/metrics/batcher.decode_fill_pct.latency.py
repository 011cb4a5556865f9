"""Rows that held a live request, as a share of the slots x useful blocks the
window's decode chunks ran."""
from perfbench import timeline


def read(ctx):
    return timeline.fill_pct(ctx, "engine.decode_rows_active", "engine.decode_rows_run")
