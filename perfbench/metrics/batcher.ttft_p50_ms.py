"""Engine-side time to first token, the median: the flight recorder's ``request.ttft_s``
(host clock when the first generated token folds) of the window's requests."""
from perfbench import loadgen, readers


def read(ctx):
    ttft = [1e3 * f["ttft_s"] for _, f in readers.flights(ctx) if "ttft_s" in f]
    return loadgen.percentile(ttft, 0.50) if ttft else None
