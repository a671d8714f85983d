"""The runtime's O(trace length) passes over its slot arrays per pass of
the window, from the profiler trace: the loop step's scan (`runtime.scan`:
the next completion and the progress up to it) and the absorber's flood
collection (`runtime.collect`: the same two scans for each event it takes
in, and a last one that finds the flood's end)."""
from bench.harness import spans

SPANS = ("runtime.scan", "runtime.collect")


def read(ctx):
    w = spans.window(ctx)
    own = [w["total_s"][name] for name in SPANS
           if w and name in w["total_s"]]
    return spans.per_pass_ms(ctx, sum(own) if own else None)
