"""The optimizer's own Python per pass: self time of `optimizer.solve` and
its children (gather, targets, place, budget), less the DRF refill and
every backend call inside them, from the profiler trace."""
from bench.harness import spans


def read(ctx):
    w = spans.window(ctx)
    own = [s for name, s in (w["self_s"].items() if w else ())
           if name.startswith("optimizer.") and name != "optimizer.refill"]
    return spans.per_pass_ms(ctx, sum(own) if own else None)
