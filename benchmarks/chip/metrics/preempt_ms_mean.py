"""Host time of one preemption (ms): the ``mm.preempt`` spans over their
count: compaction, the victim's page gathers, their copy to the host
and the host-tier bookkeeping (engine counters, host clock).  None
where the program has no spans or nothing was preempted."""


def read(ctx):
    st = ctx["stats"]
    n = getattr(st, "span_n", {}).get("mm.preempt", 0)
    return st.span_ns["mm.preempt"] / n / 1e6 if n else None
