"""Host wall time inside the jax backend's programs per pass (padding,
dispatch, transfers and the device time they wait for), spanned by the
benchmark around `place_run`, `ladder_counts` and `saturating_probe`."""


def read(ctx):
    r = ctx["run"]
    return 1e3 * r["rec"].backend_s / len(r["rec"].pass_wall)
