"""Model step: mean synced time of one batched decode step (engine stats
decode_s / decode_steps, deltas over the window)."""


def read(ctx):
    s = ctx["window"]["stats"]
    return 1e3 * s["decode_s"] / s["decode_steps"] if s["decode_steps"] \
        else None
