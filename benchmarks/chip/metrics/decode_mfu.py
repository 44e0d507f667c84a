"""Model operations of the decode steps' real rows, over the decode
program's device time, over the chip's peak (%)."""


def read(ctx):
    t = ctx["trace"]["programs"].get("decode", 0.0)
    f = ctx["work"]["decode_flops"]
    if t <= 0 or f <= 0:
        return None
    return 100.0 * f / t / ctx["peaks"]["bf16_flops"]
