"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

The SSD recurrence  S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T,
y_t = C_t . S_t  is evaluated in chunks of length Q (arXiv:2405.21060 §6):
within a chunk everything is dense matmuls (MXU work), across chunks a
small (H, P, N) state is carried — here in VMEM scratch along the
sequential chunk grid axis.  All decay exponents are non-positive
(A < 0, dt > 0), so every exp() is in (0, 1] and the kernel is stable in
f32 without max-subtraction.

Restriction: ngroups == 1 (B/C shared across heads — true for the assigned
mamba2-370m and hymba configs); ops.py falls back to the jnp reference for
G > 1.

Tiling: grid = (batch, T/Q); per step loads (Q, H, P) x, (Q, N) B/C tiles;
intra-chunk cost ~ Q^2·(N + H·P) MACs — Q=128 aligns both matmul dims with
the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, fs_ref, state,
            *, chunk: int, n_heads: int, head_dim: int, d_state: int):
    c_idx = pl.program_id(1)
    n_c = pl.num_programs(1)

    @pl.when(c_idx == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)      # (Q, H, P)
    dt = dt_ref[0].astype(jnp.float32)    # (Q, H)
    Bm = b_ref[0].astype(jnp.float32)     # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)     # (Q, N)
    A = a_ref[...].astype(jnp.float32)    # (1, H), negative

    a = dt * A                            # (Q, H) log-decay increments (<= 0)
    cum = jnp.cumsum(a, axis=0)           # inclusive
    total = cum[-1]                       # (H,)

    # ---- intra-chunk (dual / attention-like form) ----
    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)   # (Q, Q)
    # clamped: i<j decays are masked out below; see ref.py NaN-grad note
    L = jnp.exp(jnp.minimum(cum[:, None, :] - cum[None, :, :], 0.0))  # (Q,Q,H)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, 1), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, 1), 1)
    causal = ii >= jj
    W = jnp.where(causal, CB[:, :, None] * L * dt[None, :, :], 0.0)  # (Q,Q,H)
    Wh = jnp.transpose(W, (2, 0, 1))                              # (H, Q, Q)
    xh = jnp.transpose(x, (1, 0, 2))                              # (H, Q, P)
    y_intra = jax.lax.dot_general(
        Wh, xh, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )                                                             # (H, Q, P)

    # ---- contribution of the carried state ----
    S = state[...]                                                # (H, P, N)
    Ch = jnp.broadcast_to(Cm[None], (n_heads, chunk, d_state))
    CS = jax.lax.dot_general(
        Ch, S, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )                                                             # (H, Q, P)
    y_state = jnp.exp(cum).T[:, :, None] * CS

    y = jnp.transpose(y_intra + y_state, (1, 0, 2))               # (Q, H, P)
    y_ref[0] = y.astype(y_ref.dtype)

    # ---- state update ----
    w = jnp.exp(total[None, :] - cum) * dt                        # (Q, H)
    Xw = xh * w.T[:, :, None]                                     # (H, Q, P)
    Bh = jnp.broadcast_to(Bm[None], (n_heads, chunk, d_state))
    s_add = jax.lax.dot_general(
        Xw, Bh, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )                                                             # (H, P, N)
    state[...] = jnp.exp(total)[:, None, None] * S + s_add

    @pl.when(c_idx == n_c - 1)
    def _finalize():
        fs_ref[0] = state[...].astype(fs_ref.dtype)


def ssd_chunk_pallas(
    x: jnp.ndarray,        # (B, T, H, P)
    dt: jnp.ndarray,       # (B, T, H) positive
    A: jnp.ndarray,        # (H,) negative
    Bm: jnp.ndarray,       # (B, T, N)  (G=1)
    Cm: jnp.ndarray,       # (B, T, N)
    init_state: jnp.ndarray,  # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool = True,
):
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0, (T, chunk)

    kernel = functools.partial(
        _kernel, chunk=chunk, n_heads=H, head_dim=P, d_state=N
    )
    grid = (B, T // chunk)
    y, fs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, H), lambda b, c: (0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, T, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, Bm, Cm, A.reshape(1, H), init_state)
    return y, fs
