"""Host-tier traffic, both ways, per 1000 output tokens (engine
counters): fault-ins, preemption gathers and prefix parking."""


def read(ctx):
    st = ctx["stats"]
    out = st.decode_tokens + st.admit_hits + st.admit_colds
    return (st.bytes_in + st.bytes_out) / 1e6 / (out / 1e3) if out else None
