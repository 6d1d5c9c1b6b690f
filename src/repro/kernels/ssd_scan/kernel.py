"""ssd_scan — Mamba2 SSD chunked scan as a Pallas TPU kernel.

The SSD dual form splits the sequence into chunks: each chunk does three small
MXU matmuls (C·Bᵀ∘L, scores·X, C·stateᵀ) entirely in VMEM, and a (P,N) f32
running state carried across chunks in scratch — the inter-chunk linear
recurrence. Grid: (batch·heads, n_chunks) with the chunk axis minor
(sequential on TPU), so the state scratch persists exactly along the
recurrence direction.

Per-(B,H) layouts: x (S,P) dt-premultiplied, dA (S,) = dt·A, B/C (S,N).
VMEM per step @ c=256, P=64, N=128: x 64 KiB, B/C 64 KiB each, L (c,c)
256 KiB f32, state 32 KiB — comfortably < 1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm(a, b, ca: int, cb: int):
    """f32 matmul contracting ``a``'s dim ``ca`` with ``b``'s dim ``cb``, at
    full f32 precision: Mosaic's default for f32 operands is one bf16 pass
    (about 2e-3 relative error on v5e), and the recurrence carries f32 state
    and feeds its prefix sums to exp."""
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, y_ref, state_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)       # (c, P)
    da = da_ref[0].astype(jnp.float32)     # (c, 1)
    bm = b_ref[0].astype(jnp.float32)      # (c, N)
    cm = c_ref[0].astype(jnp.float32)      # (c, N)

    # Mosaic has no cumsum: the chunk's prefix sums are matmuls against
    # triangular 0/1 masks on the MXU, each produced in the layout its
    # consumer needs (a (1,1) scalar would have to broadcast across both
    # sublanes and lanes, which Mosaic cannot do)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = col <= row
    cums = _mm(tri.astype(jnp.float32), da, 1, 0)       # (c, 1) Σ_{j<=i} dA_j
    cums_row = _mm(da, tri.astype(jnp.float32), 0, 1)   # (1, c) same, as a row
    rest = _mm((col > row).astype(jnp.float32), da, 1, 0)  # (c, 1) last - cums
    last = _mm(da, jnp.ones((chunk, bm.shape[1]), jnp.float32), 0, 0)  # (1, N)
    # intra-chunk decay matrix L[i,j] = exp(cums_i - cums_j) for j <= i
    L = jnp.where(tri, jnp.exp(cums - cums_row), 0.0)

    scores = _mm(cm, bm, 1, 1) * L         # (c, c)
    y = _mm(scores, x, 1, 0)
    # inter-chunk contribution: C_l · state_prevᵀ · exp(cums_l)
    prev = state_ref[...]                  # (P, N)
    y += _mm(cm, prev, 1, 1) * jnp.exp(cums)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: state · exp(cums_last) + (x ∘ decay)ᵀ · B
    state_ref[...] = prev * jnp.exp(last) + _mm(x * jnp.exp(rest), bm, 0, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jnp.ndarray,    # (BH, S, P) — dt-premultiplied inputs
    dA: jnp.ndarray,   # (BH, S)
    Bm: jnp.ndarray,   # (BH, S, N)
    Cm: jnp.ndarray,   # (BH, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    BH, S, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dA[..., None], Bm, Cm)
