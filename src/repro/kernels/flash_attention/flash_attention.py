"""Flash attention (prefill/train) Pallas TPU kernel.

Tiling: grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the last
grid dimension is sequential on TPU, so the online-softmax state
(running max / denominator / weighted accumulator) lives in VMEM scratch
and the output block is written on the final kv step.

GQA is handled in the k/v index_map (q head h reads kv head h // group),
so no head replication is materialized.  The wrapper moves the head axis
ahead of the sequence axis, so every block's two minor dims are
(seq block, head_dim) — the layout Mosaic tiles — and the head and
batch dims are squeezed out of the kernel's view.  Causal +
sliding-window masking is computed from block offsets with iota —
masked *inside* the exponent.

VMEM budget per program (bq = bk = 512, hd <= 256, f32 compute):
q/k/v blocks 3*512*256*4 = 1.5 MB, score tile 512*512*4 = 1 MB, scratch
~0.6 MB => ~3.1 MB, comfortably under the ~16 MB VMEM of a v5e core;
matmul dims (512, hd) are MXU-aligned for hd in {64, 128, 192, 256}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, window: int, scale: float, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)              # (bq, hd)
    k = k_ref[...].astype(jnp.float32)              # (bk, hd)
    v = v_ref[...].astype(jnp.float32)              # (bk, hd)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    """q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd). Returns (B, Sq, H, hd).

    Sq/Skv must be divisible by bq/bk (callers pad).  `causal` must be
    True (decoder-only framework); window > 0 adds sliding-window masking.
    """
    assert causal, "only causal attention is used in this framework"
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, "pad sequences to block multiples"
    nq = Sq // bq
    nk = Skv // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, window=window, scale=scale, nk=nk)

    q_spec = pl.BlockSpec((None, None, bq, hd),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, h, qi, ki: (b, h // G, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),   # weighted-value accumulator
        ],
        interpret=interpret,
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
