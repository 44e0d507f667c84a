"""Real rows per decode step over the window (engine counters)."""


def read(ctx):
    st = ctx["stats"]
    return st.decode_tokens / st.decode_steps if st.decode_steps else None
