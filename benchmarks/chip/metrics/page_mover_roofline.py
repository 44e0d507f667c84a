"""Bytes the page gathers, scatters and compactions must move (pages
times page bytes, read and written), over their programs' device time,
over the chip's bandwidth (%)."""

import cost


def read(ctx):
    t = ctx["trace"]["programs"].get("page_movers", 0.0)
    b = ctx["work"]["mover_bytes"]
    if t <= 0 or b <= 0:
        return None
    return cost.roofline_share(0.0, b, t, ctx["peaks"])
