"""Model operations of the prompts' tokens, over the prefill program's
device time, over the chip's peak (%)."""


def read(ctx):
    t = ctx["trace"]["programs"].get("prefill", 0.0)
    f = ctx["work"]["prefill_flops"]
    if t <= 0 or f <= 0:
        return None
    return 100.0 * f / t / ctx["peaks"]["bf16_flops"]
