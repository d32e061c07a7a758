"""Distributed training simulation on 8 virtual devices: DP x TP mesh with
pjit + MS-EDEN NVFP4 gradient compression on the DP axis (the beyond-paper
feature: unbiased 4.5-bit gradient traffic).

    python examples/distributed_sim.py [--steps 20] [--compress]
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.dist import sharding as SH
from repro.dist.compression import compressed_grad_mean
from repro.models import lm
from repro.train.train_step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--compress", action="store_true",
                    help="NVFP4 MS-EDEN gradient all-reduce on the DP axis")
    args = ap.parse_args()

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = registry.get("llama_200m").reduced()
    corpus = SyntheticCorpus(DataConfig(vocab=cfg.vocab, seq_len=64,
                                        global_batch=8))

    grad_transform = None
    if args.compress:
        def grad_transform(grads, seed):
            # per-DP-shard quantized mean (wire: packed 4-bit + e4m3 scales)
            return shard_map(
                lambda g, s: compressed_grad_mean(g, "data", s),
                mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_vma=False)(grads, seed)

    init_state, train_step = make_train_step(
        cfg, "quartet2", base_lr=2e-3, total_steps=args.steps,
        grad_transform=grad_transform)
    state = init_state(lm.init(cfg, jax.random.PRNGKey(0)))

    with mesh:
        state_sh = SH.state_shardings(jax.eval_shape(lambda: state), mesh,
                                      fsdp=False)
        state = jax.device_put(state, state_sh)
        stepj = jax.jit(train_step, in_shardings=(state_sh, None),
                        out_shardings=(state_sh, None))
        for i in range(args.steps):
            batch = corpus.batch_at(i)
            state, m = stepj(state, batch)
            if i % 5 == 0:
                print(f"step {i} loss {float(m['loss']):.4f} "
                      f"(devices={mesh.devices.size}, "
                      f"compressed_dp={bool(args.compress)})")
    print("done — final loss", float(m["loss"]))


if __name__ == "__main__":
    main()
