"""Operations and bytes of the work the cells run.

``jaxpr_costs`` walks a function's jaxpr and counts every ``dot_general`` and
convolution as 2·M·N·K operations, multiplying the bodies of ``scan`` by
their trip count (XLA's own ``cost_analysis`` visits a loop body once), and
models HBM traffic as the operands and results of those contractions plus the
results of gathers, scatters and slice updates. It is a copy of the
program's ``repro.launch.costs.jaxpr_costs``, kept here so that no change to
the program can change how the benchmark counts.

The analytic count beside it is the textbook one for a dense decoder's train
step, ``train_flops``: 6·N per token for the matmul weights, the unembedding
included, plus 12·L·H·hd·S for attention's two contractions, forward and
backward, over the whole S×S score matrix the program computes.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.extend import core as jcore


@dataclass
class Costs:
    flops: float = 0.0
    bytes: float = 0.0
    by_prim: dict = field(default_factory=dict)

    def add(self, prim: str, flops: float, bytes_: float, mult: float) -> None:
        self.flops += flops * mult
        self.bytes += bytes_ * mult
        agg = self.by_prim.setdefault(prim, [0.0, 0.0])
        agg[0] += flops * mult
        agg[1] += bytes_ * mult


def _size_bytes(aval) -> float:
    if not hasattr(aval, "shape"):
        return 0.0
    return float(np.prod(aval.shape, dtype=np.float64)) * aval.dtype.itemsize


def _dot_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dnums
    batch = np.prod([lhs.shape[i] for i in lb], dtype=np.float64)
    contract = np.prod([lhs.shape[i] for i in lc], dtype=np.float64)
    lhs_free = np.prod(
        [d for i, d in enumerate(lhs.shape) if i not in lc and i not in lb], dtype=np.float64
    )
    rhs_free = np.prod(
        [d for i, d in enumerate(rhs.shape) if i not in rc and i not in rb], dtype=np.float64
    )
    return 2.0 * batch * contract * lhs_free * rhs_free


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    # flops = 2 · out_elems · (kernel elems per output channel)
    kernel_per_out = np.prod(rhs.shape, dtype=np.float64) / rhs.shape[-1]
    return 2.0 * np.prod(out.shape, dtype=np.float64) * kernel_per_out


# Ops whose OUTPUTS are genuine HBM writes. broadcast/iota/select are always
# fusion-resident on XLA:TPU and are deliberately NOT counted.
_MEMORY_PRIMS = {
    "gather", "scatter", "scatter-add", "dynamic_slice", "dynamic_update_slice",
    "take", "concatenate",
}


def _sub_jaxprs(eqn):
    """All jaxprs referenced by an eqn's params (generic: covers pjit/jit,
    remat2, closed_call, custom_*_call — any call-like primitive)."""
    out = []
    for v in eqn.params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, jcore.ClosedJaxpr):
                    out.append(item.jaxpr)
                elif isinstance(item, jcore.Jaxpr):
                    out.append(item)
    return out


def _walk(jaxpr, costs: Costs, mult: float) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            length = eqn.params["length"]
            inner = eqn.params["jaxpr"].jaxpr
            # carried state traffic: read+write once per iteration
            carry_bytes = sum(_size_bytes(v.aval) for v in eqn.outvars)
            costs.add("scan_carry", 0.0, carry_bytes, mult)
            _walk(inner, costs, mult * length)
        elif name == "while":
            # bounded decode loops: treat body once (not used in hot paths)
            _walk(eqn.params["body_jaxpr"].jaxpr, costs, mult)
        elif name == "cond":
            branches = eqn.params["branches"]
            sub = Costs()
            _walk(branches[0].jaxpr, sub, 1.0)
            costs.add("cond", sub.flops, sub.bytes, mult)
        elif name == "dot_general":
            io_bytes = sum(_size_bytes(v.aval) for v in (*eqn.invars, *eqn.outvars))
            costs.add(name, _dot_flops(eqn), io_bytes, mult)
        elif name == "conv_general_dilated":
            io_bytes = sum(_size_bytes(v.aval) for v in (*eqn.invars, *eqn.outvars))
            costs.add(name, _conv_flops(eqn), io_bytes, mult)
        elif name in _MEMORY_PRIMS:
            costs.add(name, 0.0, sum(_size_bytes(v.aval) for v in eqn.outvars), mult)
        elif name == "pallas_call":
            # kernel-aware: HBM traffic = the call's operands/results (tiles
            # stream through VMEM); flops = kernel body × grid size.
            io_bytes = sum(_size_bytes(v.aval) for v in (*eqn.invars, *eqn.outvars))
            grid = 1.0
            gm = eqn.params.get("grid_mapping")
            if gm is not None and getattr(gm, "grid", None):
                grid = float(np.prod([g for g in gm.grid if isinstance(g, int)]))
            sub = Costs()
            inner = eqn.params.get("jaxpr")
            if inner is not None:
                _walk(inner.jaxpr if hasattr(inner, "jaxpr") else inner, sub, 1.0)
            costs.add(name, sub.flops * grid, io_bytes, mult)
        else:
            for sub in _sub_jaxprs(eqn):
                _walk(sub, costs, mult)


def jaxpr_costs(fn, *args, **kwargs) -> Costs:
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    costs = Costs()
    _walk(closed.jaxpr, costs, 1.0)
    # program inputs/outputs cross HBM once
    io = sum(_size_bytes(v.aval) for v in (*closed.jaxpr.invars, *closed.jaxpr.outvars))
    costs.add("program_io", 0.0, io, 1.0)
    return costs


def matmul_params(arch) -> int:
    """Weights that a token meets in a contraction: every block matrix and
    the tied (padded) embedding as unembedding; the norm scales are not."""
    return sum(int(np.prod(s)) for p, s in arch.param_shapes().items()
               if not p.endswith("/scale"))


def train_flops(arch, batch: int, seq: int) -> float:
    """Forward and backward operations of one train step."""
    tokens = batch * seq
    attn = 12.0 * arch.layers * arch.heads * arch.head_dim * seq
    return (6.0 * matmul_params(arch) + attn) * tokens
