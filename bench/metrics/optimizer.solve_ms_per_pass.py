"""Solve per pass outside the DRF refill (placement, Eq-15/16 budgets, the
backend calls inside it): the delta of the master's solve timer less the
refill's. Unlike `phase_breakdown()["solve"]`, a compile inside the window
is not taken out, and nothing is clamped."""


def read(ctx):
    r = ctx["run"]
    return 1e3 * r["phase_delta"]["solve"] / len(r["rec"].pass_wall)
