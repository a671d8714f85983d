"""Share of the window's wall time spent outside the master's passes: the
runtime's event loop and absorber on the host (host clock, bench spans)."""


def read(ctx):
    r = ctx["run"]
    return 100.0 * (r["window_s"] - sum(r["rec"].pass_wall)) / r["window_s"]
