"""Master bookkeeping per pass: enforcement, Eq-1/2/4 metrics and flood
merging, from the deltas of the master's phase timers over the window."""


def read(ctx):
    r = ctx["run"]
    passes = len(r["rec"].pass_wall)
    d = r["phase_delta"]
    return 1e3 * (d["enforce"] + d["metrics"] + d["absorb"]) / passes
