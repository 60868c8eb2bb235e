"""Regression tests for the §Perf hillclimb fixes (EXPERIMENTS.md §Perf).

Each of these locked in a large dry-run win; a regression would silently
re-replicate terabytes on the production mesh, so they are asserted at
the unit level (no 512-device mesh needed).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get, get_smoke
from repro.launch.mesh import make_host_mesh
from repro.models import init_caches, init_model
from repro.models.model import cache_axes, lm_loss
from repro.sharding.rules import DEFAULT_ACT_RULES, constrain, spec_for


class TestCacheSharding:
    """§Perf/qwen-decode iteration 1: KV caches must shard with ACT rules
    (cache_batch -> data, cache_seq -> model), never silently replicate."""

    def test_kv_cache_spec_shards_batch_and_seq(self):
        mesh = make_host_mesh()
        axes = ("layers", "cache_batch", "cache_seq", "kv_heads", None)
        spec = spec_for(axes, (64, 128, 32768, 40, 128), mesh,
                        DEFAULT_ACT_RULES)
        assert spec[1] == "data"
        assert spec[2] == "model"
        # kv_heads must NOT claim model again (one mesh axis per spec)
        assert spec[3] is None

    def test_launch_cache_shardings_not_replicated(self):
        from repro.launch.specs import _abstract_caches, _cache_shardings
        cfg = get("qwen1.5-32b")
        mesh = make_host_mesh()
        sds = _abstract_caches(cfg, 128, 32768)
        sh = _cache_shardings(cfg, sds, mesh)
        spec = sh["kv"].k.spec
        assert "data" in spec and "model" in spec, (
            f"KV cache replicated again: {spec}")


class TestPaddedVocab:
    """§Perf/internvl2-train iteration 1: odd vocabs pad to x128 so the
    LM head shards; padded logit columns are masked to -inf."""

    def test_padded_vocab_multiple_of_128(self):
        for name in ("internvl2-1b", "mamba2-780m", "hymba-1.5b",
                     "phi3.5-moe-42b-a6.6b"):
            cfg = get(name)
            assert cfg.padded_vocab % 128 == 0
            assert cfg.padded_vocab >= cfg.vocab
            assert cfg.padded_vocab - cfg.vocab < 128

    def test_param_shapes_use_padded_vocab(self):
        cfg = get_smoke("internvl2-1b")
        params = jax.eval_shape(
            lambda k: init_model(k, cfg).params, jax.random.PRNGKey(0))
        assert params["embed"].shape[0] == cfg.padded_vocab

    def test_padded_logits_masked(self):
        import dataclasses
        cfg = dataclasses.replace(get_smoke("internvl2-1b"), vocab=1000)
        assert cfg.padded_vocab == 1024
        model = init_model(jax.random.PRNGKey(0), cfg)
        from repro.models.model import forward_train
        toks = jnp.zeros((1, 8), jnp.int32)
        logits, _ = forward_train(model.params, cfg, toks, remat=False)
        pad = np.asarray(logits[..., cfg.vocab:])
        assert np.all(np.isneginf(pad)), "padding columns must be -inf"
        assert np.all(np.isfinite(np.asarray(logits[..., :cfg.vocab])))

    def test_loss_finite_with_padding(self):
        cfg = get_smoke("internvl2-1b")
        model = init_model(jax.random.PRNGKey(1), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)
        loss = lm_loss(model.params, cfg, toks, toks, remat=False)
        assert np.isfinite(float(loss))


class TestConstrain:
    """§Perf/internvl2-train iteration 2: logical-axis sharding constraint
    helper — must be a no-op outside a mesh and apply inside one."""

    def test_noop_outside_mesh(self):
        x = jnp.ones((4, 8))
        y = constrain(x, "batch", None)
        assert y is x or np.array_equal(np.asarray(y), np.asarray(x))

    def test_applies_inside_mesh(self):
        mesh = make_host_mesh()

        def f(x):
            return constrain(x, "batch", None) * 2

        with mesh:
            out = jax.jit(f)(jnp.ones((4, 8)))
        np.testing.assert_allclose(np.asarray(out), 2.0)


class TestCacheAxesTree:
    def test_cache_axes_match_cache_tree(self):
        cfg = get_smoke("hymba-1.5b")
        caches = jax.eval_shape(lambda: init_caches(cfg, 2, 32))
        axes = cache_axes(cfg)
        # every cache leaf has a same-rank logical-axes tuple
        leaves = jax.tree.leaves(caches)
        axleaves = jax.tree.leaves(
            axes, is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        assert len(leaves) == len(axleaves)
        for leaf, ax in zip(leaves, axleaves):
            assert len(leaf.shape) == len(ax)
