"""The backend's blocking copies of program outputs to the host per pass
(`backend.*.wait`: the device time, plus the transfer), from the profiler
trace."""
from bench.harness import spans


def read(ctx):
    w = spans.window(ctx)
    own = [s for name, s in (w["total_s"].items() if w else ())
           if name.startswith("backend.") and name.endswith(".wait")]
    return spans.per_pass_ms(ctx, sum(own) if own else None)
