"""Whole-request swap-outs per request completed in the window (engine
counters)."""


def read(ctx):
    done = sum(1 for s in ctx["sent"] if s.req.done)
    return ctx["stats"].swaps_out / done if done else None
