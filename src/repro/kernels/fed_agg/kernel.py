"""fed_agg / fed_opt — fused aggregation kernels over stacked client flats.

The paper's hot loop: FedAvg's Σ_k (n_k/n)·w_k over K client parameter
vectors (eq. 1). On a serving/training silo this runs over the *entire*
flattened model (up to 10^11 elements) each federation round, so it is a
pure memory-bandwidth problem: tile the flat parameter axis into VMEM-sized
columns and compute each output tile as a (1,K)×(K,BN) matmul — one pass over
HBM, no intermediate (K,N) temporaries like the naive jnp formulation.

``fed_agg`` accepts arbitrary per-client coefficients (not just normalized
example weights), which is what lets FedAvg / FedBuff / PartialFedAvg /
FedAsync's factorized lerp chain all share one kernel. For fleets wider than
``BK`` clients the (K, N) stack is streamed in (BK, BN) tiles with on-chip
accumulation — the kernel never needs K full rows resident at once, so
10^8-param × hundreds-of-clients aggregations stay within VMEM.

``fed_opt`` fuses the adaptive-strategy chain (Reddi et al. 2021):
avg → pseudo-gradient Δ = x − avg → moment updates (adam/yogi/adagrad) →
server step, in a single pass over each (K, BN) stripe — five elementwise
passes and one matvec collapse into one HBM read per operand.

Layout: stacked (K, N) f32, weights (K,) f32, state vectors (N,) f32.
Block: (K, BN) with BN = 64·128 lanes → K·BN·4 B ≤ 2 MiB VMEM for K ≤ 64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 8192  # flat-axis tile (64 × 128 lanes)
BK = 64    # client-axis tile: wider fleets stream K in BK-row stripes


def _wsum(w, x):
    # (1, K) @ (K, BN) — lands on the MXU; full f32 passes, since the result
    # is a model's weights and the kernel is bound by HBM, not by the MXU
    return jax.lax.dot_general(
        w.T, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _fed_agg_kernel(w_ref, x_ref, o_ref):
    # x: (K, BN) f32 block; w: (K, 1) f32 (full); o: (1, BN)
    o_ref[...] = _wsum(w_ref[...], x_ref[...])


def _fed_agg_acc_kernel(w_ref, x_ref, o_ref):
    # K-tiled: same output tile revisited across the k grid axis; init at
    # k == 0, then accumulate each (BK, BN) stripe's partial weighted sum.
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _wsum(w_ref[...], x_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fed_agg(stacked: jnp.ndarray, weights: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """stacked: (K, N) f32; weights: (K,) f32 → (N,) f32 = weightsᵀ·stacked."""
    K, N = stacked.shape
    stacked = stacked.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    pad = (-N) % BN
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Np = N + pad
    if K <= BK:
        out = pl.pallas_call(
            _fed_agg_kernel,
            grid=(Np // BN,),
            in_specs=[
                pl.BlockSpec((K, 1), lambda i: (0, 0)),       # weights, every tile
                pl.BlockSpec((K, BN), lambda i: (0, i)),      # one column stripe
            ],
            out_specs=pl.BlockSpec((1, BN), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
            interpret=interpret,
        )(weights[:, None], stacked)
        return out[0, :N]
    # Stream the client axis: zero-padded rows contribute nothing (their
    # weight is zero), and the k grid axis is innermost so each output tile
    # finishes its accumulation before the next column stripe starts.
    padk = (-K) % BK
    if padk:
        stacked = jnp.pad(stacked, ((0, padk), (0, 0)))
        weights = jnp.pad(weights, (0, padk))
    Kp = K + padk
    out = pl.pallas_call(
        _fed_agg_acc_kernel,
        grid=(Np // BN, Kp // BK),
        in_specs=[
            pl.BlockSpec((BK, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((BK, BN), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, BN), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=interpret,
    )(weights[:, None], stacked)
    return out[0, :N]


def _opt_step(avg, p, m, v, *, lr, b1, b2, tau, variant):
    """Δ → moments → server step on one (1, BN) tile; shared by the fused and
    the two-pass (wide-fleet) fed_opt variants."""
    d = p - avg                                  # pseudo-gradient Δ
    m = b1 * m + (1.0 - b1) * d
    d2 = d * d
    if variant == "adam":
        v = b2 * v + (1.0 - b2) * d2
    elif variant == "yogi":
        v = v - (1.0 - b2) * d2 * jnp.sign(v - d2)
    elif variant == "adagrad":
        v = v + d2
    else:
        raise ValueError(f"unknown fed_opt variant {variant!r}")
    return p - lr * m / (jnp.sqrt(v) + tau), m, v


def _fed_opt_kernel(w_ref, x_ref, p_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                    *, lr, b1, b2, tau, variant):
    """One (K, BN) stripe of the fused adaptive-aggregation chain."""
    avg = _wsum(w_ref[...], x_ref[...])         # (1, BN) weighted mean
    po_ref[...], mo_ref[...], vo_ref[...] = _opt_step(
        avg, p_ref[...], m_ref[...], v_ref[...],
        lr=lr, b1=b1, b2=b2, tau=tau, variant=variant)


def _fed_opt_apply_kernel(a_ref, p_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                          *, lr, b1, b2, tau, variant):
    """Elementwise pass over a precomputed weighted mean — the second stage of
    the wide-fleet (K > BK) path, where the mean comes from the K-streaming
    fed_agg so no more than a (BK, BN) stripe is ever resident."""
    po_ref[...], mo_ref[...], vo_ref[...] = _opt_step(
        a_ref[...], p_ref[...], m_ref[...], v_ref[...],
        lr=lr, b1=b1, b2=b2, tau=tau, variant=variant)


@functools.partial(jax.jit,
                   static_argnames=("lr", "b1", "b2", "tau", "variant", "interpret"))
def fed_opt(stacked: jnp.ndarray, weights: jnp.ndarray, x: jnp.ndarray,
            m: jnp.ndarray, v: jnp.ndarray, *, lr: float, b1: float, b2: float,
            tau: float, variant: str = "adam",
            interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused FedAdam/FedYogi/FedAdagrad step (Reddi et al. 2021):

        avg = weightsᵀ·stacked;  Δ = x − avg
        m' = b1·m + (1−b1)·Δ;    v' = variant(v, Δ²)
        x' = x − lr·m' / (√v' + tau)

    Returns (x', m', v'), all (N,) f32. ``lr``/``b1``/``b2``/``tau`` are
    compile-time constants (hyperparameters). Fleets wider than ``BK`` run
    the two-pass route — K-streaming ``fed_agg`` for the mean, then one
    fused elementwise pass — so no more than a (BK, BN) stripe is ever
    resident in VMEM."""
    K, N = stacked.shape
    pad = (-N) % BN
    row = lambda a: a.astype(jnp.float32)[None, :]
    hp = dict(lr=float(lr), b1=float(b1), b2=float(b2), tau=float(tau),
              variant=variant)
    vec = lambda: pl.BlockSpec((1, BN), lambda i: (0, i))
    x, m, v = row(x), row(m), row(v)
    if pad:
        x, m, v = (jnp.pad(a, ((0, 0), (0, pad))) for a in (x, m, v))
    Np = N + pad
    if K > BK:
        avg = fed_agg(stacked, weights, interpret=interpret)
        avg = avg[None, :]
        if pad:
            avg = jnp.pad(avg, ((0, 0), (0, pad)))
        xo, mo, vo = pl.pallas_call(
            functools.partial(_fed_opt_apply_kernel, **hp),
            grid=(Np // BN,),
            in_specs=[vec(), vec(), vec(), vec()],
            out_specs=[vec(), vec(), vec()],
            out_shape=[jax.ShapeDtypeStruct((1, Np), jnp.float32)] * 3,
            interpret=interpret,
        )(avg, x, m, v)
        return xo[0, :N], mo[0, :N], vo[0, :N]
    stacked = stacked.astype(jnp.float32)
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    xo, mo, vo = pl.pallas_call(
        functools.partial(_fed_opt_kernel, **hp),
        grid=(Np // BN,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, BN), lambda i: (0, i)),
            vec(), vec(), vec(),
        ],
        out_specs=[vec(), vec(), vec()],
        out_shape=[jax.ShapeDtypeStruct((1, Np), jnp.float32)] * 3,
        interpret=interpret,
    )(weights.astype(jnp.float32)[:, None], stacked, x, m, v)
    return xo[0, :N], mo[0, :N], vo[0, :N]
