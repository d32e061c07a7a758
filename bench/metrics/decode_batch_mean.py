"""Scheduler: tokens per decode step (decode_tokens / decode_steps, deltas
over the window): how full the decode batch runs."""


def read(ctx):
    s = ctx["window"]["stats"]
    return s["decode_tokens"] / s["decode_steps"] if s["decode_steps"] \
        else None
