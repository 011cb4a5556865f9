"""Tokens the device loop generated in the window, by its own counter: the
check of ``tokens_per_s`` from the other side of the edge."""
from perfbench import readers


def read(ctx):
    return readers.counter_rate(ctx, "engine.generated_tokens_device")
