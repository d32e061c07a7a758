"""Substrate tests: optimizers, schedules, data determinism, checkpointing,
fault-tolerant trainer (bitwise resume), NVFP4 gradient compression."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import registry
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.models import lm
from repro.optim import adamw, muon, schedules
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = registry.get("llama_200m").reduced()
    corpus = SyntheticCorpus(DataConfig(vocab=cfg.vocab, seq_len=32,
                                        global_batch=4, seed=3))
    init_state, train_step = make_train_step(
        cfg, "quartet2", base_lr=1e-3, total_steps=50, base_seed=1)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    return cfg, corpus, init_state, jax.jit(train_step), params


class TestOptim:
    def test_adamw_converges_quadratic(self):
        p = {"w": jnp.asarray([5.0, -3.0])}
        st = adamw.init(p)
        for _ in range(300):
            g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(p)
            p, st = adamw.update(g, st, p, lr=0.05, weight_decay=0.0)
        assert float(jnp.abs(p["w"]).max()) < 0.1

    def test_muon_newton_schulz_orthogonalizes(self):
        """Muon's 5-step NS is deliberately approximate: singular values land
        in a band around 1 (Jordan et al. report ~[0.7, 1.2]), not exactly 1."""
        g = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        s_in = np.linalg.svd(np.asarray(g), compute_uv=False)
        o = muon.newton_schulz(g)
        s_out = np.linalg.svd(np.asarray(o), compute_uv=False)
        assert s_in.max() / s_in.min() > 3          # input is ill-conditioned
        assert 0.3 < s_out.min() and s_out.max() < 1.4  # output is near-orthogonal

    def test_muon_partition(self):
        params = {"embed": jnp.zeros((8, 4)), "stages": {"w": jnp.zeros((4, 4))},
                  "norm": jnp.zeros((4,))}
        mask = muon.partition_mask(params)
        assert mask["stages"]["w"] and not mask["embed"] and not mask["norm"]

    def test_schedules(self):
        lr = schedules.warmup_cosine(0, base_lr=1.0, total_steps=100)
        assert float(lr) == 0.0
        lr_mid = schedules.warmup_cosine(55, base_lr=1.0, total_steps=100)
        lr_end = schedules.warmup_cosine(99, base_lr=1.0, total_steps=100)
        assert float(lr_mid) > float(lr_end) >= 0
        w = schedules.wsd(50, base_lr=1.0, total_steps=100)
        assert float(w) == 1.0  # stable phase


class TestData:
    def test_deterministic_and_resumable(self):
        c = SyntheticCorpus(DataConfig(vocab=128, seq_len=16, global_batch=4))
        a = c.batch_at(7)
        b = c.batch_at(7)
        assert np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
        assert not np.array_equal(np.asarray(a["tokens"]),
                                  np.asarray(c.batch_at(8)["tokens"]))

    def test_sharding_partitions_batch(self):
        c = SyntheticCorpus(DataConfig(vocab=128, seq_len=16, global_batch=8))
        s0 = c.batch_at(3, shard_id=0, num_shards=2)
        s1 = c.batch_at(3, shard_id=1, num_shards=2)
        assert s0["tokens"].shape == (4, 16)
        assert not np.array_equal(np.asarray(s0["tokens"]), np.asarray(s1["tokens"]))

    def test_labels_are_shifted_tokens(self):
        c = SyntheticCorpus(DataConfig(vocab=128, seq_len=16, global_batch=2))
        b = c.batch_at(0)
        assert np.array_equal(np.asarray(b["tokens"][:, 1:]),
                              np.asarray(b["labels"][:, :-1]))

    def test_bigram_structure_learnable(self):
        """Perfect bigram predictions must beat unigram entropy (the corpus
        has signal, so QAT loss gaps are meaningful)."""
        c = SyntheticCorpus(DataConfig(vocab=64, seq_len=128, global_batch=8))
        b = c.batch_at(0)
        toks = np.asarray(b["tokens"]).reshape(-1)
        perm = np.asarray(c._perm)
        hits = (perm[toks[:-1]] == toks[1:]).mean()
        assert hits > 0.3  # ~half the transitions follow the bigram kernel


class TestCheckpointer:
    def test_roundtrip_and_gc(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                 "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        for s in (1, 2, 3):
            ck.save(s, state, {"tag": s})
        assert ck.all_steps() == [2, 3]  # gc keeps last 2
        like = jax.tree.map(lambda x: jnp.zeros_like(x), state)
        restored, meta = ck.restore(like)
        assert meta["step"] == 3
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(state["a"]))

    def test_async_save(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(5, {"x": jnp.ones((128, 128))}, blocking=False)
        ck.wait()
        assert ck.latest_step() == 5

    def test_atomicity_no_partial_dirs(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, {"x": jnp.ones((4,))})
        assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


class TestTrainerFaultTolerance:
    def test_bitwise_resume(self, tmp_path, tiny_setup):
        """Crash at step 6, restore, continue — must equal the uninterrupted
        run bitwise (deterministic data + step-seeded quantization)."""
        cfg, corpus, init_state, train_step, params = tiny_setup

        def fresh():
            return init_state(jax.tree.map(jnp.copy, params))

        # uninterrupted 10 steps
        s = fresh()
        for i in range(10):
            s, _ = train_step(s, corpus.batch_at(i))
        ref_leaf = np.asarray(jax.tree.leaves(s.params)[0])

        # interrupted at 6 + resumed
        tcfg = TrainerConfig(total_steps=6, ckpt_dir=str(tmp_path / "ck"),
                             ckpt_every=1000, log_every=1000, async_ckpt=False)
        tr = Trainer(tcfg, train_step, corpus)
        s2 = tr.run(fresh(), resume=False)          # saves final ckpt at 6
        tcfg2 = TrainerConfig(total_steps=10, ckpt_dir=str(tmp_path / "ck"),
                              ckpt_every=1000, log_every=1000, async_ckpt=False)
        tr2 = Trainer(tcfg2, train_step, corpus)
        s3 = tr2.run(fresh(), resume=True)          # restores step 6 -> 10
        out_leaf = np.asarray(jax.tree.leaves(s3.params)[0])
        np.testing.assert_array_equal(ref_leaf, out_leaf)

    def test_emergency_checkpoint_on_exception(self, tmp_path, tiny_setup):
        cfg, corpus, init_state, train_step, params = tiny_setup

        calls = {"n": 0}

        def exploding_step(state, batch):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("simulated node failure")
            return train_step(state, batch)

        tcfg = TrainerConfig(total_steps=10, ckpt_dir=str(tmp_path / "ck"),
                             ckpt_every=1000, log_every=1000, async_ckpt=False)
        tr = Trainer(tcfg, exploding_step, corpus)
        with pytest.raises(RuntimeError):
            tr.run(init_state(params), resume=False)
        assert tr.ckpt.latest_step() is not None  # emergency ckpt exists

    def test_elastic_restore_different_structure_checks(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, {"a": jnp.ones((4,))})
        with pytest.raises(AssertionError):
            ck.restore({"a": jnp.ones((4,)), "b": jnp.ones((2,))})


class TestGradCompression:
    def test_compressed_mean_is_accurate_and_unbiased(self):
        """shard_map NVFP4 all-reduce ~= exact mean; averaging over seeds
        converges (unbiasedness)."""
        # the container exposes one device; run the 4-way mesh in a
        # subprocess with forced host-platform devices
        import subprocess, sys, textwrap
        code = textwrap.dedent('''
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp, numpy as np
            from jax import shard_map
            from jax.sharding import Mesh, PartitionSpec as P
            from repro.dist.compression import compressed_psum_mean
            mesh = Mesh(np.asarray(jax.devices()), ("data",))
            x = jax.random.normal(jax.random.PRNGKey(0), (4, 2048), jnp.float32)
            want = jnp.mean(x, axis=0)
            f = jax.jit(shard_map(
                lambda xs, seed: compressed_psum_mean(xs[0], "data", seed),
                mesh=mesh, in_specs=(P("data", None), P()), out_specs=P(),
                check_vma=False))
            outs = jnp.stack([f(x, jnp.asarray([5, i], jnp.uint32)) for i in range(32)])
            one = float(jnp.linalg.norm(outs[0] - want) / jnp.linalg.norm(want))
            avg = jnp.mean(outs, 0)
            many = float(jnp.linalg.norm(avg - want) / jnp.linalg.norm(want))
            assert one < 0.2, one
            assert many < one / 2, (one, many)
            print("OK", one, many)
        ''')
        env = dict(os.environ, PYTHONPATH="src")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, cwd=os.getcwd())
        assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr

    def test_wire_bytes_are_4bit(self):
        """The all_to_all payload is packed uint8 nibbles + fp8 scales."""
        from repro.core import formats as F
        codes = jnp.zeros((4, 256), jnp.uint8)
        packed = F.pack_fp4(codes)
        bits_per_elem = (packed.size * 8 + (256 // 16) * 4 * 8) / (4 * 256)
        assert bits_per_elem <= 4.5
