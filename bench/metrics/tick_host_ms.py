"""Engine loop: host time per serving tick. Over the program's `serve.tick`
spans that lie wholly in the traced window, the mean of (tick duration -
time in the `serve.sync` spans inside it): admission, input arrays, table
uploads, dispatch, sampling, token reads, flushes and gauges, during which
the chip waits (bench/lib/xplane_meta.py reads the spans from the trace)."""

from bench.lib import xplane_meta


def read(ctx):
    tr = xplane_meta.with_meta(ctx["window"]["trace"])
    if tr is None:
        return None
    host = xplane_meta.tick_host_s(tr)
    return 1e3 * sum(host) / len(host) if host else None
