"""Substrate tests: optimizer math, data pipeline, checkpointing,
serving engine generation, sharding rules."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import ServeConfig, TrainConfig
from repro.configs import get_smoke
from repro.data import DataConfig, make_batches
from repro.launch.mesh import make_host_mesh
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.models import init_model
from repro.serving import generate
from repro.training import adamw
from repro.training.train_step import init_train_state, train_step


class TestAdamW:
    def test_single_step_matches_reference_math(self):
        tc = TrainConfig(lr=1e-2, warmup_steps=0, total_steps=10**9,
                         weight_decay=0.0, grad_clip=1e9)
        p = {"w": jnp.asarray([1.0, 2.0], jnp.float32)}
        g = {"w": jnp.asarray([0.1, -0.2], jnp.float32)}
        st0 = adamw.init(p)
        newp, st1, _ = adamw.apply(st0, g, tc, jnp.float32)
        # bias-corrected adam first step: update = lr * g/|g| elementwise
        m = (1 - 0.9) * np.asarray(g["w"])
        v = (1 - 0.95) * np.asarray(g["w"]) ** 2
        mh = m / (1 - 0.9)
        vh = v / (1 - 0.95)
        expect = np.asarray(p["w"]) - 1e-2 * mh / (np.sqrt(vh) + tc.eps)
        np.testing.assert_allclose(np.asarray(newp["w"]), expect, rtol=1e-5)
        assert int(st1.step) == 1

    def test_weight_decay_pulls_toward_zero(self):
        tc = TrainConfig(lr=1e-2, warmup_steps=0, weight_decay=0.5,
                         total_steps=10**9)
        p = {"w": jnp.asarray([10.0], jnp.float32)}
        g = {"w": jnp.asarray([0.0], jnp.float32)}
        newp, _, _ = adamw.apply(adamw.init(p), g, tc, jnp.float32)
        assert float(newp["w"][0]) < 10.0

    def test_grad_clip_limits_update(self):
        tc = TrainConfig(lr=1.0, warmup_steps=0, grad_clip=1e-3,
                         weight_decay=0.0, total_steps=10**9)
        p = {"w": jnp.ones((4,), jnp.float32)}
        g = {"w": jnp.full((4,), 100.0, jnp.float32)}
        _, st1, m = adamw.apply(adamw.init(p), g, tc, jnp.float32)
        assert float(m["grad_norm"]) == pytest.approx(200.0, rel=1e-4)
        # clipped: m should be tiny
        assert float(jnp.abs(st1.m["w"]).max()) < 1e-3

    def test_lr_schedule_shape(self):
        tc = TrainConfig(lr=1e-3, warmup_steps=10, total_steps=100)
        lrs = [float(adamw.lr_schedule(tc, s)) for s in [0, 5, 10, 50, 100]]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(5e-4)
        assert lrs[2] == pytest.approx(1e-3)
        assert lrs[3] < 1e-3
        assert lrs[4] == pytest.approx(1e-4, rel=1e-2)

    def test_microbatched_grads_match_whole_batch(self):
        cfg = get_smoke("stablelm-1.6b")
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
        model = init_model(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        tc1 = TrainConfig(microbatches=1)
        tc4 = TrainConfig(microbatches=4)
        s1, m1 = train_step(init_train_state(model, tc1), batch, cfg, tc1)
        s4, m4 = train_step(init_train_state(model, tc4), batch, cfg, tc4)
        assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-4)
        d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         s1.params, s4.params)
        assert max(jax.tree.leaves(d)) < 1e-4


class TestTrainingLoop:
    def test_loss_decreases_on_structured_data(self):
        cfg = get_smoke("stablelm-1.6b")
        tc = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=60)
        model = init_model(jax.random.PRNGKey(0), cfg)
        state = init_train_state(model, tc)
        data = make_batches(DataConfig(vocab=cfg.vocab, seq_len=64, batch=8))
        step = jax.jit(lambda s, b: train_step(s, b, cfg, tc))
        losses = []
        for i, b in zip(range(60), data):
            state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()})
            losses.append(float(m["loss"]))
        assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.3, losses[::10]


class TestData:
    def test_shapes_and_range(self):
        it = make_batches(DataConfig(vocab=512, seq_len=64, batch=4))
        b = next(it)
        assert b["tokens"].shape == (4, 64) and b["labels"].shape == (4, 64)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 512

    def test_labels_are_shifted_tokens(self):
        it = make_batches(DataConfig(vocab=128, seq_len=16, batch=2))
        b = next(it)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_rank_sharding_differs(self):
        b0 = next(make_batches(DataConfig(vocab=128, seq_len=16, batch=2, rank=0)))
        b1 = next(make_batches(DataConfig(vocab=128, seq_len=16, batch=2, rank=1)))
        assert not np.array_equal(b0["tokens"], b1["tokens"])

    def test_deterministic_by_seed(self):
        b0 = next(make_batches(DataConfig(vocab=128, seq_len=16, batch=2, seed=7)))
        b1 = next(make_batches(DataConfig(vocab=128, seq_len=16, batch=2, seed=7)))
        np.testing.assert_array_equal(b0["tokens"], b1["tokens"])


class TestCheckpoint:
    def test_roundtrip_nested_state(self):
        cfg = get_smoke("qwen1.5-32b")
        model = init_model(jax.random.PRNGKey(0), cfg)
        state = init_train_state(model, TrainConfig())
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 3, state, {"arch": cfg.name})
            assert latest_step(d) == 3
            zeroed = jax.tree.map(jnp.zeros_like, state)
            restored = restore_checkpoint(d, 3, zeroed)
            ok = jax.tree.map(
                lambda a, b: bool(jnp.allclose(a.astype(jnp.float32),
                                               b.astype(jnp.float32))),
                restored, state)
            assert all(jax.tree.leaves(ok))

    def test_missing_key_raises(self):
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 0, {"a": jnp.ones(3)})
            with pytest.raises(ValueError):
                restore_checkpoint(d, 0, {"a": jnp.ones(3), "b": jnp.ones(2)})


class TestServingEngine:
    def test_greedy_generation_deterministic_and_valid(self):
        cfg = get_smoke("stablelm-1.6b")
        model = init_model(jax.random.PRNGKey(0), cfg)
        sc = ServeConfig(max_seq=96, temperature=0.0)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab)
        o1 = generate(model.params, cfg, sc, prompt, 12)
        o2 = generate(model.params, cfg, sc, prompt, 12)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        assert o1.shape == (2, 12)
        assert np.asarray(o1).min() >= 0 and np.asarray(o1).max() < cfg.vocab

    def test_generation_matches_stepwise_forward(self):
        """Greedy generate == repeated argmax over full forward (the
        engine's cache path against the no-cache oracle)."""
        import dataclasses
        from repro.models import forward_train
        cfg = dataclasses.replace(get_smoke("stablelm-1.6b"), dtype="float32")
        model = init_model(jax.random.PRNGKey(0), cfg)
        sc = ServeConfig(max_seq=64, temperature=0.0)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab)
        gen = np.asarray(generate(model.params, cfg, sc, prompt, 6))[0]
        seq = np.asarray(prompt)[0].tolist()
        for _ in range(6):
            logits, _ = forward_train(
                model.params, cfg, jnp.asarray([seq]), None, remat=False)
            seq.append(int(jnp.argmax(logits[0, -1])))
        np.testing.assert_array_equal(gen, seq[6:])


class TestShardingRules:
    def test_divisibility_fallback(self):
        os.environ.setdefault("XLA_FLAGS", "")
        from repro.sharding.rules import spec_for
        from jax.sharding import PartitionSpec as P
        mesh = make_host_mesh()
        # heads=14 not divisible by model=1? (1 divides everything) -> kept
        assert spec_for(("embed", "heads"), (896, 14), mesh) == P(("data",), "model")

    @given(dim=st.sampled_from([14, 25, 96, 128]),
           axis=st.sampled_from(["heads", "mlp", "vocab"]))
    @settings(max_examples=12, deadline=None)
    def test_property_never_invalid(self, dim, axis):
        from repro.sharding.rules import spec_for
        mesh = make_host_mesh()
        spec = spec_for((axis,), (dim,), mesh)
        size = 1  # all axes size 1 in this mesh
        assert dim % size == 0  # trivially consistent; exercised on 512-dev
                                # meshes in the dry-run itself
