"""Flash-decode Pallas TPU kernel: ONE query token per sequence against a
long KV cache, with an explicit validity mask (ring-buffer / linear cache
semantics come in via `valid`, computed by the serving layer).

Tiling: grid = (batch, q_heads, num_kv_blocks); kv blocks stream through
VMEM while the online-softmax state sticks in scratch. The query row is
tiny ((G, hd) after GQA folding) so the kernel is HBM-bandwidth-bound by
K/V traffic — exactly the regime the roofline analysis shows for
decode_32k, which is why this is a kernel-worthy hot spot.

The wrapper moves the head axis ahead of the sequence axis and gives
the query and the validity mask a unit row axis, so every block's two
minor dims are either (kv block, head_dim) or (1, lanes) — the layouts
Mosaic tiles — and batch/head dims are squeezed out of the kernel's view.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bk: int, scale: float, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                # (1, hd)
    k = k_ref[...].astype(jnp.float32)                # (bk, hd)
    v = v_ref[...].astype(jnp.float32)                # (bk, hd)
    valid = valid_ref[...]                            # (1, bk) int32

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (1, bk)
    s = jnp.where(valid > 0, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (1, hd)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, valid, *, bk: int = 1024,
                     interpret: bool = False):
    """q: (B, H, hd); k/v: (B, S, KV, hd); valid: (S,) bool.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    bk = min(bk, S)
    assert S % bk == 0, "cache length must be a multiple of the kv block"
    nk = S // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, bk=bk, scale=scale, nk=nk)
    valid_i = valid.astype(jnp.int32)[None, :]               # (1, S)
    q_spec = pl.BlockSpec((None, None, 1, hd), lambda b, h, ki: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, h, ki: (b, h // G, ki, 0))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((1, bk), lambda b, h, ki: (0, ki)),
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q[:, :, None, :], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
      valid_i)
    return out[:, :, 0, :]
