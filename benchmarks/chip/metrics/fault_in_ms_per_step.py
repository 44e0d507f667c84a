"""Host time of fault-in per engine step (ms): the ``tier.fault_in``
spans over the number of ``engine.step`` spans (engine counters, host
clock).  None where the program has no spans."""


def read(ctx):
    st = ctx["stats"]
    n = getattr(st, "span_n", {}).get("engine.step", 0)
    if not n:
        return None
    return st.span_ns.get("tier.fault_in", 0) / n / 1e6
