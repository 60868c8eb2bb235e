"""Per-kernel allclose sweeps (interpret=True on CPU) against the pure-jnp
oracles, over shapes and dtypes (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Optional dev dependency: conftest.py installs a deterministic fallback
# shim when the real library is absent, so this normally never skips.
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.sched_score.ops import sched_score_argmax, sched_score_topb
from repro.kernels.sched_score.ref import (
    sched_score_argmax_ref,
    sched_score_topb_ref,
)
from repro.kernels.ssd_scan.ops import ssd_intra
from repro.kernels.ssd_scan.ref import ssd_intra_ref

TOLS = {jnp.float32: dict(atol=3e-5, rtol=3e-5),
        jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,KV,hd,window,bq,bk",
        [
            (1, 512, 4, 4, 64, 0, 128, 128),     # MHA
            (2, 512, 8, 2, 64, 0, 256, 128),     # GQA
            (1, 1024, 4, 1, 128, 0, 256, 256),   # MQA, wide head
            (1, 512, 4, 2, 64, 200, 128, 128),   # sliding window
            (1, 768, 6, 3, 32, 0, 256, 256),     # non-pow2 heads
        ])
    def test_matches_oracle(self, dtype, B, S, H, KV, hd, window, bq, bk):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (B, S, H, hd), dtype)
        k = rand(ks[1], (B, S, KV, hd), dtype)
        v = rand(ks[2], (B, S, KV, hd), dtype)
        out = flash_attention(q, k, v, window=window, bq=bq, bk=bk)
        ref = flash_attention_ref(q, k, v, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOLS[dtype])

    def test_block_shape_invariance(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = rand(ks[0], (1, 1024, 4, 64), jnp.float32)
        k = rand(ks[1], (1, 1024, 2, 64), jnp.float32)
        v = rand(ks[2], (1, 1024, 2, 64), jnp.float32)
        o1 = flash_attention(q, k, v, bq=128, bk=256)
        o2 = flash_attention(q, k, v, bq=512, bk=512)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,KV,hd,n_valid,bk",
        [
            (1, 1024, 8, 8, 64, 1000, 256),
            (4, 2048, 8, 2, 64, 1, 512),         # single valid entry
            (2, 1024, 16, 2, 128, 555, 256),
            (1, 4096, 4, 1, 64, 4096, 1024),     # fully valid, MQA
        ])
    def test_matches_oracle(self, dtype, B, S, H, KV, hd, n_valid, bk):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (B, H, hd), dtype)
        k = rand(ks[1], (B, S, KV, hd), dtype)
        v = rand(ks[2], (B, S, KV, hd), dtype)
        valid = jnp.arange(S) < n_valid
        out = decode_attention(q, k, v, valid, bk=bk)
        ref = decode_attention_ref(q, k, v, valid)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOLS[dtype])

    def test_ring_mask_pattern(self):
        """Non-contiguous validity (ring cache wrap) handled exactly."""
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        B, S, H, KV, hd = 1, 512, 4, 2, 64
        q = rand(ks[0], (B, H, hd), jnp.float32)
        k = rand(ks[1], (B, S, KV, hd), jnp.float32)
        v = rand(ks[2], (B, S, KV, hd), jnp.float32)
        valid = (jnp.arange(S) % 3) != 1
        out = decode_attention(q, k, v, valid, bk=128)
        ref = decode_attention_ref(q, k, v, valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


class TestSSDScan:
    @pytest.mark.parametrize(
        "B,nc,Q,H,P,N",
        [
            (1, 2, 32, 2, 16, 16),
            (2, 4, 64, 4, 32, 32),
            (1, 1, 128, 8, 64, 128),   # mamba2-780m native tile
            (2, 3, 16, 5, 8, 24),      # odd head count
        ])
    def test_matches_oracle(self, B, nc, Q, H, P, N):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        xc = jax.random.normal(ks[0], (B, nc, Q, H, P), jnp.float32)
        Bc = jax.random.normal(ks[1], (B, nc, Q, N)) * 0.5
        Cc = jax.random.normal(ks[2], (B, nc, Q, N)) * 0.5
        dtc = jax.nn.softplus(jax.random.normal(ks[3], (B, nc, Q, H)))
        A = jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
        cum = jnp.cumsum(-A[None, None, None, :] * dtc, axis=2)
        y1, s1 = ssd_intra(xc, Bc, Cc, dtc, cum)
        y2, s2 = ssd_intra_ref(xc, Bc, Cc, dtc, cum)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-4, rtol=1e-4)

    def test_end_to_end_through_model_path(self):
        """ssd_chunked(impl='pallas') == ssd_chunked(impl='xla')."""
        from repro.models.ssm import ssd_chunked
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        B, S, H, P, N = 2, 96, 3, 16, 16
        x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
        Bm = jax.random.normal(ks[1], (B, S, N)) * 0.5
        Cm = jax.random.normal(ks[2], (B, S, N)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
        A = jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
        y1, s1 = ssd_chunked(x, Bm, Cm, dt, A, chunk=32, impl="pallas")
        y2, s2 = ssd_chunked(x, Bm, Cm, dt, A, chunk=32, impl="xla")
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


class TestSchedScore:
    @given(seed=st.integers(0, 1000), nb=st.sampled_from([1, 2, 8]),
           density=st.floats(0.01, 1.0))
    @settings(max_examples=25, deadline=None)
    def test_property_matches_oracle(self, seed, nb, density):
        n = 512 * nb
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        wait = jax.random.uniform(ks[0], (n,)) * 1e4
        cost = jax.random.uniform(ks[1], (n,)) * 4000 + 16
        urg = jax.random.uniform(ks[2], (n,)) * 2
        mask = jax.random.bernoulli(ks[3], density, (n,))
        w = jnp.asarray([1.0, 0.6, 0.8, 512.0])
        i1, s1 = sched_score_argmax(wait, cost, urg, mask, w, blk=512)
        i2, s2 = sched_score_argmax_ref(wait, cost, urg, mask, w)
        assert float(s1) == pytest.approx(float(s2), rel=1e-5)
        if bool(mask.any()):
            assert bool(mask[int(i1)])

    def test_all_masked_returns_sentinel(self):
        n = 512
        z = jnp.zeros((n,))
        w = jnp.asarray([1.0, 0.6, 0.8, 512.0])
        i, s = sched_score_argmax(z, z + 100, z, jnp.zeros((n,), bool), w)
        assert float(s) <= -1e29


class TestSchedScoreTopB:
    """Fused partial top-B vs the `lax.top_k` oracle: exact index AND
    exact score equality, including first-occurrence tie-breaking — the
    property the windowed scheduler's bit-exact contract rests on."""

    W = jnp.asarray([1.0, 0.8, 0.5, 650.0], jnp.float32)

    def _features(self, n, seed, density=0.7):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        wait = jax.random.uniform(ks[0], (n,)) * 5e3
        cost = jax.random.uniform(ks[1], (n,)) * 3000 + 0.5
        urg = jax.random.uniform(ks[2], (n,)) * 2
        mask = jax.random.bernoulli(ks[3], density, (n,))
        return wait, cost, urg, mask

    def _check(self, n, b, blk=2048, seed=0, density=0.7):
        wait, cost, urg, mask = self._features(n, seed, density)
        ik, sk = sched_score_topb(wait, cost, urg, mask, self.W, b, blk=blk)
        ir, sr = sched_score_topb_ref(wait, cost, urg, mask, self.W,
                                      min(b, n))
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))

    @given(seed=st.integers(0, 1000), nb=st.sampled_from([1, 2, 5]),
           b=st.sampled_from([1, 4, 16]), density=st.floats(0.0, 1.0))
    @settings(max_examples=20, deadline=None)
    def test_property_matches_topk(self, seed, nb, b, density):
        self._check(512 * nb, b, blk=512, seed=seed, density=density)

    @pytest.mark.parametrize("n", [7, 96, 130, 1000, 5000])
    def test_non_lane_aligned_lengths(self, n):
        """Queue lengths that are not multiples of the TPU lane width or
        the block size exercise the mask=False padding in ops.py."""
        self._check(n, min(8, n), blk=512, seed=3)

    def test_window_sized_queues(self):
        """Window capacities the engine actually uses, aligned or not."""
        for w in (96, 128, 192, 4096):
            self._check(w, 16, blk=1024, seed=4)

    def test_tie_breaking_first_occurrence(self):
        """Duplicate feature rows produce exact score ties; the kernel
        must rank equal scores by ascending index like lax.top_k."""
        n, half = 512, 256
        wait, cost, urg, _ = self._features(n, seed=9, density=1.0)
        wait = wait.at[half:].set(wait[:half])
        cost = cost.at[half:].set(cost[:half])
        urg = urg.at[half:].set(urg[:half])
        mask = jnp.ones((n,), bool)
        ik, sk = sched_score_topb(wait, cost, urg, mask, self.W, 32, blk=128)
        ir, sr = sched_score_topb_ref(wait, cost, urg, mask, self.W, 32)
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))

    def test_b_exceeds_eligible(self):
        """b far above the eligible count: the exhausted region must
        still mirror top_k (first-occurrence over masked sentinels)."""
        self._check(64, 32, blk=128, seed=5, density=0.05)
        self._check(100, 16, seed=6, density=0.0)  # nothing eligible

    def test_b_equals_n(self):
        self._check(16, 16, seed=7)

    def test_fifo_weight_row_matches_topk_on_arrival(self):
        """The FIFO emulation (weights [1,0,0,1], -arrival in the wait
        slot) must reproduce lax.top_k(-arrival) exactly — this is the
        rank_fifo pallas path."""
        n, b = 300, 8
        arrival = jax.random.uniform(jax.random.PRNGKey(8), (n,)) * 1e5
        mask = jax.random.bernoulli(jax.random.PRNGKey(9), 0.5, (n,))
        w_fifo = jnp.asarray([1.0, 0.0, 0.0, 1.0], jnp.float32)
        ones, zeros = jnp.ones((n,)), jnp.zeros((n,))
        ik, _ = sched_score_topb(-arrival, ones, zeros, mask, w_fifo, b)
        key = jnp.where(mask, arrival, jnp.inf)
        _, ir = jax.lax.top_k(-key, b)
        live = np.asarray(mask.sum())
        np.testing.assert_array_equal(
            np.asarray(ik)[:live], np.asarray(ir)[:live])


class TestSchedScoreRoute:
    """Route-term parity: every sched_score kernel with a (5,) weights
    vector and a route feature row must match its oracle exactly — the
    fleet scheduler's endpoint-aware score rides this fifth term."""

    W5 = jnp.asarray([1.0, 0.8, 0.5, 650.0, 400.0], jnp.float32)

    def _features(self, n, seed, density=0.7):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        wait = jax.random.uniform(ks[0], (n,)) * 5e3
        cost = jax.random.uniform(ks[1], (n,)) * 3000 + 0.5
        urg = jax.random.uniform(ks[2], (n,)) * 2
        mask = jax.random.bernoulli(ks[3], density, (n,))
        route = jax.random.uniform(ks[4], (n,)) * 3.0
        return wait, cost, urg, mask, route

    @given(seed=st.integers(0, 1000), density=st.floats(0.01, 1.0))
    @settings(max_examples=15, deadline=None)
    def test_argmax_matches_oracle(self, seed, density):
        wait, cost, urg, mask, route = self._features(512, seed, density)
        i1, s1 = sched_score_argmax(wait, cost, urg, mask, self.W5,
                                    route, blk=512)
        i2, s2 = sched_score_argmax_ref(wait, cost, urg, mask, self.W5,
                                        route)
        assert float(s1) == float(s2)
        if bool(mask.any()):
            assert int(i1) == int(i2)

    @given(seed=st.integers(0, 1000), b=st.sampled_from([1, 8, 16]),
           density=st.floats(0.0, 1.0))
    @settings(max_examples=15, deadline=None)
    def test_topb_matches_oracle(self, seed, b, density):
        wait, cost, urg, mask, route = self._features(512, seed, density)
        ik, sk = sched_score_topb(wait, cost, urg, mask, self.W5, b,
                                  route, blk=512)
        ir, sr = sched_score_topb_ref(wait, cost, urg, mask, self.W5, b,
                                      route)
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))

    def test_route_none_matches_four_weight(self):
        """Omitting route with a (4,) weights vector is the pre-fleet
        path — it must stay byte-identical to passing route=None."""
        wait, cost, urg, mask, _ = self._features(512, seed=3)
        w4 = self.W5[:4]
        i1, s1 = sched_score_topb(wait, cost, urg, mask, w4, 8, blk=512)
        i2, s2 = sched_score_topb(wait, cost, urg, mask, w4, 8, None,
                                  blk=512)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    def test_zero_route_weight_matches_no_route(self):
        """w_route == 0 with an arbitrary route row ranks identically to
        the route-free kernel (score algebra appends `- 0 * route`,
        which is exact in float)."""
        wait, cost, urg, mask, route = self._features(512, seed=5)
        w5 = jnp.asarray([1.0, 0.8, 0.5, 650.0, 0.0], jnp.float32)
        ik, sk = sched_score_topb(wait, cost, urg, mask, w5, 8, route,
                                  blk=512)
        ir, sr = sched_score_topb(wait, cost, urg, mask, w5[:4], 8,
                                  blk=512)
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


class TestInterpretMode:
    """Compiled on TPU, interpreted on CPU, and nothing silent elsewhere:
    a mis-detected device must not quietly run the interpreter."""

    @pytest.mark.parametrize("backend,expect", [
        ("cpu", True), ("tpu", False), ("gpu", RuntimeError)])
    def test_backend_switch(self, monkeypatch, backend, expect):
        from repro.kernels import interpret_mode

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if expect is RuntimeError:
            with pytest.raises(RuntimeError, match="gpu"):
                interpret_mode()
        else:
            assert interpret_mode() is expect
