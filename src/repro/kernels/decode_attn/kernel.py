"""Pallas TPU kernel: flash-decoding GQA attention over the paged KV cache.

Serving hot spot: one new query token per sequence attends over a (possibly
huge) KV cache.  This is the compute layer under the TurboKV-routed cache —
pages land on shards via the directory; each shard runs this kernel over its
resident pages and partial softmax stats are combined across shards
(flash-decoding), see ``serving/engine.py``.

Tiling: grid = (batch, S/block_s); the S axis is the innermost (sequential)
grid dimension, carrying the online-softmax running (m, l, acc) in VMEM
scratch.  Per step the kernel loads one (block_s, Hkv, D) K/V tile and the
(Hq, D) query tile; scores are a per-kv-head batched MXU matmul with the
group dimension folded in (GQA: G = Hq / Hkv query heads share a kv head).

Supports a sliding window (gemma3 local layers) via position masking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_scr, l_scr, acc_scr,
            *, block_s: int, n_kv: int, group: int, head_dim: int,
            window: int | None, scale: float):
    s_idx = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (Hq, D)
    k = k_ref[0].astype(jnp.float32)                  # (Sb, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    length = len_ref[0, 0]

    qg = q.reshape(n_kv, group, head_dim)             # (Hkv, G, D)
    kt = jnp.transpose(k, (1, 0, 2))                  # (Hkv, Sb, D)
    vt = jnp.transpose(v, (1, 0, 2))

    # batched over kv heads: (Hkv, G, D) x (Hkv, Sb, D) -> (Hkv, G, Sb)
    scores = jax.lax.dot_general(
        qg, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )

    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_s), 2)
    valid = pos < length
    if window is not None:
        valid &= pos >= (length - window)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)   # (Hkv, G, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)                       # (Hkv, G, Sb)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    # (Hkv, G, Sb) x (Hkv, Sb, D) -> (Hkv, G, D)
    pv = jax.lax.dot_general(
        p, vt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    acc_new = corr * acc_prev + pv

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(s_idx == n_s - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        out = (acc_scr[...] / denom).reshape(n_kv * group, head_dim)
        o_ref[0] = out.astype(o_ref.dtype)


def decode_attn_pallas(
    q: jnp.ndarray,        # (B, Hq, D)
    k: jnp.ndarray,        # (B, S, Hkv, D)
    v: jnp.ndarray,        # (B, S, Hkv, D)
    lengths: jnp.ndarray,  # (B,) int32 valid KV length per sequence
    *,
    block_s: int = 512,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool = True,
):
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    assert S % block_s == 0, (S, block_s)

    kernel = functools.partial(
        _kernel, block_s=block_s, n_kv=Hkv, group=group, head_dim=D,
        window=window, scale=scale,
    )
    grid = (B, S // block_s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, block_s, Hkv, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, block_s, Hkv, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, s: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, lengths.reshape(B, 1).astype(jnp.int32))
    return out
