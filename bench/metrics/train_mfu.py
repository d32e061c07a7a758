"""Train step: model FLOPs per token (6 x (non-embedding params + head) +
6 x seq x d x layers of causal attention; bench/lib/flops.py; the
Hadamard transforms and quantizers not counted) x train_tok_s over the
cell's chips x one chip's bf16 peak."""

from bench.lib import flops


def read(ctx):
    per_tok = flops.train_token_flops(ctx["model"], ctx["trainer"]["seq"])
    return 100.0 * ctx["train_tok_s"] * per_tok / (
        ctx["chips"] * ctx["peaks"]["flops_bf16"])
