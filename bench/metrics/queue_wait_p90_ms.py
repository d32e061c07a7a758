"""Scheduler: 90th percentile of queued -> admitted (RequestTrace spans)
over the requests submitted in the window."""

from bench.lib import stats


def read(ctx):
    waits = [t.queue_wait_s for t in ctx["window"]["traces"]
             if t.queue_wait_s is not None]
    return 1e3 * stats.percentile(waits, 90) if waits else None
