"""Device: share of the traced window in which no op ran on the chip."""

from bench.lib import report


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - report.busy_s(tr) / tr["window_s"])
