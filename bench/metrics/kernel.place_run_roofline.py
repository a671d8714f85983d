"""`place_run`'s share of its memory roofline: the least bytes the
schedules must move (free capacity and 1/cap read once, 2*b*m*8; item
demands, K*m*8; grants written, K*b*8; unpadded shapes, no O(b^2) compare
grid) over the chip's HBM bandwidth, divided by the programs' device time.
No compute bound: the VPU's peak is not published."""


def read(ctx):
    tr = ctx["trace"]
    p = tr and tr["programs"].get("place_run")
    if not p or not p["device_s"]:
        return None
    t_min = ctx["run"]["rec"].place_run_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * t_min / p["device_s"]
