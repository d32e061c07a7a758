"""What decides `correct` for a serving cell.

Once the window has closed and the program's state is freed, the plain
reference that the configuration names (bench/lib/ref_dense.py for the
dense models) runs once over each sampled request's
prompt followed by its served tokens. For served token t at position p the
gap is max(ref logits at p-1) - ref logit of t: zero when the program
served the reference's best token. The widest gap over the sample is held
to the cell's limit; a request that failed or never finished fails the
run outright.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.lib import cell as C

PAD = 128


def _batch(sample):
    s = max(len(p) + len(t) for p, t in sample)
    s = -(-s // PAD) * PAD
    tokens = np.zeros((len(sample), s), np.int32)
    valid = np.zeros((len(sample), s), bool)
    for i, (p, t) in enumerate(sample):
        seq = list(p) + list(t)
        tokens[i, :len(seq)] = seq
        valid[i, :len(seq)] = True
    return jnp.asarray(tokens), jnp.asarray(valid)


def _positions(sample):
    """(row, position predicting the token, token) of every served token."""
    rows, pos, toks = [], [], []
    for i, (p, t) in enumerate(sample):
        for j, tok in enumerate(t):
            rows.append(i)
            pos.append(len(p) + j - 1)
            toks.append(tok)
    return np.asarray(rows), np.asarray(pos), np.asarray(toks)


def model_kw(config: dict) -> dict:
    """How the configuration's scheme and served weight type reach the
    reference."""
    return {"quant": config["engine"]["scheme"] != "bf16",
            "dtype": C.module(config["weights"]).SERVED_DTYPE}


def gaps(ref, m: dict, seed: int, sample, kw: dict,
         chosen=None) -> np.ndarray:
    """Reference gap of each served token (or of `chosen` tokens, one per
    served position)."""
    tokens, valid = _batch(sample)
    rows, pos, toks = _positions(sample)
    if chosen is not None:
        toks = np.asarray(chosen)
    lg = ref.logits(m, seed, tokens, valid, **kw)[rows, pos]
    return np.asarray(jnp.max(lg, -1) - lg[np.arange(len(toks)), toks])


def control_choice(ref, m: dict, seed: int, sample, kw: dict,
                   act: str) -> np.ndarray:
    """The token the reference computed at lower precision puts first at
    each served position (teacher-forced on the same prompts and tokens)."""
    tokens, valid = _batch(sample)
    rows, pos, _ = _positions(sample)
    lg = ref.logits(m, seed, tokens, valid, act=act, **kw)[rows, pos]
    return np.asarray(jnp.argmax(lg, -1))


def compare(ref, m: dict, seed: int, sample, check: dict, failed: int,
            kw: dict) -> dict:
    """`ref`: the reference module (its `logits`)."""
    numbers = {"failed_requests": {"value": failed, "limit": 0}}
    if sample:
        g = gaps(ref, m, seed, sample, kw)
        numbers["served_tokens"] = {"value": int(g.size),
                                    "limit": check["served_tokens"]}
        numbers["gap_max"] = {"value": float(g.max()),
                              "limit": check["gap_max_limit"]}
    correct = (failed == 0 and bool(sample)
               and numbers["served_tokens"]["value"] >= check["served_tokens"]
               and numbers["gap_max"]["value"] <= check["gap_max_limit"])
    return {"correct": correct, "numbers": numbers}
