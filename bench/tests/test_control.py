"""The lower-precision control reads past each cell's limit, at a size a
test run holds: the reference computed one step below the precision the
configuration states, put in the program's place.

- Serving (`granite-8b-bf16.chat`, bf16 weights and activations): the
  reference with every activation rounded to float8 e4m3; at each served
  position of the same prompts and tokens, the gap of the token it puts
  first, under the float32 reference.
- Training (`yi-9b-train.seq2k`, float32 weights, gradients and Adam
  state): the reference with all of those in bfloat16, against the
  float32 reference.
"""

import numpy as np

from bench.lib import check_serve, check_train, gen, ref_dense, ref_train, train
from bench.tests import tiny


def test_serve_control_fails():
    c = tiny.cell("granite-8b-bf16.chat", n_layers=12, d_model=512,
                  n_heads=4, n_kv_heads=1, head_dim=128, d_ff=1536,
                  vocab=4096)
    m, kw = c.config["model"], check_serve.model_kw(c.config)
    r = gen.rng(11, 0)
    sample = [(r.integers(1, m["vocab"], 40).tolist(),
               r.integers(1, m["vocab"], 24).tolist()) for _ in range(4)]
    ref = ref_dense
    choice = check_serve.control_choice(ref, m, 11, sample, kw, "fp8")
    gaps = check_serve.gaps(ref, m, 11, sample, kw, chosen=choice)
    assert gaps.max() > c.traffic["check"]["gap_max_limit"]
    # the reference's own first choice reads exactly 0
    own = check_serve.control_choice(ref, m, 11, sample, kw, "f32")
    assert np.all(check_serve.gaps(ref, m, 11, sample, kw, chosen=own) == 0)


def test_train_control_fails():
    c = tiny.cell("yi-9b-train.seq2k")
    m, t = c.config["model"], c.config["trainer"]
    tr = train.Trainer(c.config, 13)
    batches = tr.batches(train.CHECK_STEPS)
    del tr
    ref = ref_train.run(m, 13, t, batches)
    ctl = ref_train.run(m, 13, t, batches, dtype="bfloat16")
    res = check_train.compare(ctl, ref, c.traffic["check"])
    assert not res["correct"], res["numbers"]
