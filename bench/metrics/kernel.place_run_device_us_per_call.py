"""Device time of one `place_run` program execution (the schedule's scan
with the Pallas best-fit kernel inside), from the profiler trace."""


def read(ctx):
    tr = ctx["trace"]
    p = tr and tr["programs"].get("place_run")
    if not p or not p["count"]:
        return None
    return 1e6 * p["device_s"] / p["count"]
