"""decode_attention — flash-decode: one query token vs a long ring cache.

Serving hot spot for decode_32k / long_500k: a single token's GQA attention
over a KV cache of up to 512k slots. The (B·KV) axis is the major grid dim;
the cache is tiled along its ring axis (minor, sequential) with online-softmax
scratch — identical math to flash_attention but with a (G, hd) query tile and
a slot-validity mask instead of causal masking (ring slots may be empty or
out-of-window; the mask comes precomputed from slot_pos).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_SUBLANES = 8


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (Gp, hd)
    k = k_ref[0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    valid = valid_ref[...][:, 0] != 0          # (bk,)

    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                  # (Gp, bk)
    scores = jnp.where(valid[None, :], scores, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ci == nc - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jnp.ndarray,       # (B, KV, G, hd)
    k: jnp.ndarray,       # (B, C, KV, hd)
    v: jnp.ndarray,
    valid: jnp.ndarray,   # (C,) bool — precomputed ring-slot validity
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, KV, G, hd = q.shape
    C = k.shape[1]
    block_k = min(block_k, C)
    assert C % block_k == 0, (C, block_k)
    # Mosaic cannot reduce a score tile of fewer than 8 sublanes (G = 1 for
    # every MHA model), so the query tile is padded to whole sublane groups
    # with zero rows; their outputs are sliced away below
    Gp = -(-G // _SUBLANES) * _SUBLANES
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, Gp - G), (0, 0))).reshape(B * KV, Gp, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, C, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, C, hd)
    validf = valid.astype(jnp.int32)[:, None]

    out = pl.pallas_call(
        _decode_kernel,
        grid=(B * KV, C // block_k),
        in_specs=[
            pl.BlockSpec((1, Gp, hd), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((block_k, 1), lambda b, c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Gp, hd), lambda b, c: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, Gp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Gp, hd), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, validf)
    return out[:, :G].reshape(B, KV, G, hd)
