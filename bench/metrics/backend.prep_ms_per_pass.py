"""The backend's host work around its programs per pass: padding and
schedule arrays (`backend.*.prep`), the jitted call until it returns
(`.dispatch`) and writing grants into the allocation (`.apply`), from the
profiler trace."""
from bench.harness import spans

PARTS = (".prep", ".dispatch", ".apply")


def read(ctx):
    w = spans.window(ctx)
    own = [s for name, s in (w["total_s"].items() if w else ())
           if name.startswith("backend.") and name.endswith(PARTS)]
    return spans.per_pass_ms(ctx, sum(own) if own else None)
