"""DRF refill per pass (the saturating probe, or the full ladder), from the
delta of the optimizer's `refill_s` over the window."""


def read(ctx):
    r = ctx["run"]
    return 1e3 * r["phase_delta"]["drf_refill"] / len(r["rec"].pass_wall)
