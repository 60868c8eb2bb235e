"""Mamba2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch, chunk, head) grid cell with chunk length Q, head
dim P, state dim N (all VMEM-resident; Q=128, P=64, N=128 => ~0.5 MB):

  decay[t,s] = exp(cum[t] - cum[s]) masked to s <= t
  W[t,s]     = (C_t . B_s) * decay[t,s] * dt[s]
  y_intra    = W @ x                       (Q,Q)@(Q,P) MXU matmul
  state      = (exp(cum[Q-1] - cum) * dt * x)^T @ B   (P,Q)@(Q,N)

The inter-chunk recurrence stays a lax.scan in repro.models.ssm (it is
O(nc) tiny matvecs — not kernel-worthy); this kernel replaces the
quadratic intra-chunk part, which dominates SSD FLOPs.

The wrapper moves the head axis ahead of the chunk axis and hands the
per-step dt/cum vectors in both orientations — (1, Q) rows and (Q, 1)
columns — so every block's two minor dims are full (Q, P), (Q, N),
(P, N), (1, Q) or (Q, 1) tiles and the kernel never transposes a
vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, b_ref, c_ref, dt_row_ref, cum_row_ref, dt_col_ref,
            cum_col_ref, y_ref, st_ref, *, Q: int):
    x = x_ref[...]                    # (Q, P) f32
    Bm = b_ref[...]                   # (Q, N)
    Cm = c_ref[...]                   # (Q, N)
    dt_row = dt_row_ref[...]          # (1, Q)
    cum_row = cum_row_ref[...]        # (1, Q)
    dt_col = dt_col_ref[...]          # (Q, 1)
    cum_col = cum_col_ref[...]        # (Q, 1)

    seg = cum_col - cum_row                                 # (Qt, Qs)
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # mask inside the exponent (avoids inf*0 in the backward pass)
    decay = jnp.exp(jnp.where(si <= ti, seg, -1e9))

    kernel = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (Qt, Qs)
    W = kernel * decay * dt_row
    y_ref[...] = jax.lax.dot_general(
        W, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    tail = jnp.exp(cum_col[Q - 1:Q, :] - cum_col) * dt_col  # (Q, 1)
    xw = x * tail                                           # (Q, P)
    st_ref[...] = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (P, N)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra(xc, Bc, Cc, dtc, cum, *, interpret: bool = False):
    """xc: (B,nc,Q,H,P) f32; Bc/Cc: (B,nc,Q,N); dtc/cum: (B,nc,Q,H).
    Returns (y_intra: (B,nc,Q,H,P), chunk_state: (B,nc,H,P,N)), both f32."""
    B, nc, Q, H, P = xc.shape
    N = Bc.shape[-1]
    kernel = functools.partial(_kernel, Q=Q)

    def head_tile(r, c):  # (B, nc, H, r, c) array -> per-head (r, c) tile
        return pl.BlockSpec((None, None, None, r, c),
                            lambda b, k, h: (b, k, h, 0, 0))

    bn_spec = pl.BlockSpec((None, None, Q, N), lambda b, k, h: (b, k, 0, 0))
    dt_h = dtc.transpose(0, 1, 3, 2)                         # (B, nc, H, Q)
    cum_h = cum.transpose(0, 1, 3, 2)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, nc, H),
        in_specs=[
            head_tile(Q, P), bn_spec, bn_spec,
            head_tile(1, Q), head_tile(1, Q), head_tile(Q, 1),
            head_tile(Q, 1),
        ],
        out_specs=[head_tile(Q, P), head_tile(P, N)],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(xc.transpose(0, 1, 3, 2, 4), Bc, Cc, dt_h[..., None, :],
      cum_h[..., None, :], dt_h[..., None], cum_h[..., None])
    return y.transpose(0, 1, 3, 2, 4), st
