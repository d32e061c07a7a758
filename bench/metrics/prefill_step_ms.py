"""Model step: mean synced time of one prefill chunk forward (engine stats
prefill_s / prefill_steps, deltas over the window)."""


def read(ctx):
    s = ctx["window"]["stats"]
    return 1e3 * s["prefill_s"] / s["prefill_steps"] if s["prefill_steps"] \
        else None
