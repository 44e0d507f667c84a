"""Least time of the decode steps (the larger of operations over peak
and bytes over bandwidth; bytes are the weights, the valid KV read and
the KV written) over the decode program's device time (%)."""

import cost


def read(ctx):
    t = ctx["trace"]["programs"].get("decode", 0.0)
    w = ctx["work"]
    if t <= 0 or w["decode_calls"] == 0:
        return None
    return cost.roofline_share(w["decode_flops"], w["decode_bytes"], t,
                               ctx["peaks"])
