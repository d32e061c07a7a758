"""Whole serving step: model FLOPs of the tokens the engine computed in the
window (prompt chunks and decode tokens of active rows, padding rows not
counted; bench/lib/flops.py) over window x the cell's chips x one chip's
bf16 peak."""

from bench.lib import flops


def read(ctx):
    w, m = ctx["window"], ctx["model"]
    total = sum(flops.forward_flops(m, size, pos, act)
                for _, size, pos, act in w["forwards"])
    if not total:
        return None
    return 100.0 * total / (w["seconds"] * ctx["chips"]
                            * ctx["peaks"]["flops_bf16"])
