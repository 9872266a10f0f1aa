"""Gradient compression for the data-parallel all-reduce.

Int8 quantized all-reduce with error feedback: each DP rank quantizes its
local gradient shard to int8 with a per-tensor scale, the psum runs over the
int8-decoded values (8x less link traffic on the wire — on TPU we model
this as the collective operating on the quantized representation), and the
quantization error is fed back into the next step's gradient (error
feedback keeps SGD convergence, 1-bit-Adam style).

Used inside a shard_map wrapper over the DP axes when
``TrainConfig.grad_compression`` is on; the error-feedback buffers ride in
the train state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray):
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum(grads, err, axis_names):
    """Quantize+psum each gradient leaf with error feedback.

    grads/err: local pytrees (inside shard_map).  Returns (mean_grads,
    new_err).  The psum itself must run on f32 (int8 psum would overflow and
    scales differ per rank), so the compression models the *wire* format:
    what is reduced is the dequantized int8 value; the information loss (and
    its error-feedback correction) is bit-accurate to an int8 collective.
    """

    def one(g, e):
        gf = g.astype(jnp.float32) + e.astype(jnp.float32)
        q, scale = quantize_int8(gf)
        deq = dequantize_int8(q, scale)
        new_e = gf - deq
        total = jax.lax.psum(deq, axis_names)
        n = jax.lax.axis_size(axis_names)
        return (total / n).astype(g.dtype), new_e.astype(e.dtype)

    out = jax.tree.map(one, grads, err)
    g_new = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    e_new = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    return g_new, e_new


def init_error_feedback(params, dtype: str = "bfloat16"):
    dt = jnp.dtype(dtype)
    return jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)
