"""Share of the traced slice in which no operation ran on the device."""
from perfbench import readers


def read(ctx):
    return readers.idle_pct(ctx)
