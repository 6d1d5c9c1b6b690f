"""flash_attention — causal (optionally sliding-window) fused attention.

TPU adaptation of FlashAttention: grid (batch·kv_heads, q_blocks, k_blocks)
with the k axis innermost (sequential on TPU), online-softmax statistics
(m, l) and the output accumulator kept in VMEM scratch across k steps.
Q/K/V tiles are MXU-aligned (block_q × head_dim, block_k × head_dim); the
(S, S) score matrix never exists — each step materializes one
(G·block_q, block_k) tile in VMEM.

GQA layout: q (B, KV, G, S, hd) — the G query heads of one KV group are
folded into the q tile so a single K/V load serves all of them.

Sliding window and causality are handled by masking (functional everywhere,
incl. interpret mode); fully-masked tiles are cheap but not skipped — block
pruning is an XLA-level scheduling concern noted in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, seq_len: int, window: int, softscale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]              # (G*block_q, hd)
    k = k_ref[0]                 # (block_k, hd)
    v = v_ref[0]
    scores = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * softscale                # (G*block_q, block_k)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) % block_q
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    mask = kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, S, KV, G, hd)
    k: jnp.ndarray,   # (B, S, KV, hd)
    v: jnp.ndarray,
    *,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, KV, G, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = 1.0 / math.sqrt(hd)
    # fold (B, KV) into the grid's major axis; interleave G at block level so
    # one K/V tile serves all G query heads of its KV group
    qf = (
        q.transpose(0, 2, 1, 3, 4)                   # (B, KV, S, G, hd)
        .reshape(B * KV, S // block_q, block_q, G, hd)
        .transpose(0, 1, 3, 2, 4)                     # (BKV, nq, G, bq, hd)
        .reshape(B * KV, S // block_q, G * block_q, hd)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_len=S,
        window=window, softscale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, G * block_q, hd), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G * block_q, hd), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, S // block_q, G * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, hd), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = (
        out.reshape(B * KV, S // block_q, G, block_q, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, KV, G, S, hd)
        .transpose(0, 3, 1, 2, 4)
    )
    return out
