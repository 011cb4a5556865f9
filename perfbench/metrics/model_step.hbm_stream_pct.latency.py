"""Weight bytes the window's decode steps had to stream, as a share of the
chip's memory bandwidth."""
from perfbench import readers


def read(ctx):
    return readers.hbm_stream_pct(ctx)
