"""Host time per engine step (ms): the ``engine.step`` spans less the
``device.wait`` spans inside them (the host waiting for a token), over
the number of steps (engine counters, host clock).  None where the
program has no spans."""


def read(ctx):
    st = ctx["stats"]
    n = getattr(st, "span_n", {}).get("engine.step", 0)
    if not n:
        return None
    ns = st.span_ns
    return (ns["engine.step"] - ns.get("device.wait", 0)) / n / 1e6
