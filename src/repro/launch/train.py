"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama_200m --reduced \
        --scheme quartet2 --steps 500 --ckpt /tmp/run1

On a real multi-host TPU job this binary runs once per host (jax.distributed
initializes from the TPU environment); here it drives the same code paths on
CPU. Checkpoints are mesh-elastic (see checkpoint/)."""

from __future__ import annotations

import argparse

import jax

from repro.configs import registry
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.launch.compile_cache import configure_compile_cache
from repro.models import lm
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the architecture")
    ap.add_argument("--scheme", default="quartet2")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "muon"])
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd"])
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    configure_compile_cache()
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    corpus = SyntheticCorpus(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        d_model=cfg.d_model, emit_embeds=cfg.input_mode == "embeds"))
    init_state, train_step = make_train_step(
        cfg, args.scheme, optimizer=args.optimizer, schedule=args.schedule,
        base_lr=args.lr, total_steps=args.steps,
        microbatches=args.microbatches)
    state = init_state(lm.init(cfg, jax.random.PRNGKey(0)))
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                      ckpt_every=max(args.steps // 5, 50), log_every=10),
        jax.jit(train_step), corpus)
    trainer.run(state, resume=args.resume)


if __name__ == "__main__":
    main()
