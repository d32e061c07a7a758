"""Quantizers: device time of the ops under the program's `quant.fwd`,
`quant.bwd` or `rht` scopes (the op's tf_op path in the trace;
bench/lib/xplane_meta.py) as a share of the chip's busy time in the traced
window. A fused op counts where its path puts it."""

from bench.lib import report, xplane_meta


def read(ctx):
    tr = xplane_meta.with_meta(ctx["trace"])
    if tr is None:
        return None
    by_scope = xplane_meta.device_s_by_scope(tr)
    if not any(s in by_scope for s in xplane_meta.QUANTIZER_SCOPES):
        return None     # a program without the scopes
    busy = report.busy_s(tr)
    quant = sum(by_scope.get(s, 0.0) for s in xplane_meta.QUANTIZER_SCOPES)
    return 100.0 * quant / busy if busy > 0 else None
