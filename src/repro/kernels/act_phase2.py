"""Pallas TPU kernel: fused ACT Phase-2/3 constrained pour.

The paper's GPU implementation performs k-1 separate passes over the
residual database matrix (eqs. 6-8), re-reading X from HBM every round. On
TPU we stream each (bn, bh) block of the database once, run the whole
k-round water-filling pour in VMEM/VREGs (the per-entry capacity ladder
Wg and cost ladder Zg ride along in the same block), and reduce to the
per-row transport cost in a single pass: HBM traffic / k vs the paper.

The pour itself uses the exclusive-prefix formulation (mathematically equal
to the sequential min/subtract rounds): r_l = clip(x - sum_{u<l} W_u, 0, W_l).
k is static and small, so the l-loop is unrolled Python.

The grid carries a query-batch dimension as its outermost (parallel) axis:
the residual-weight blocks of x are shared across queries while each query
streams its own (cost, capacity) ladders, so a batch of queries pours in
one kernel launch.

The ladders are RUNG-MAJOR, (nq, k, n, hmax): rung l of a block is one
(bn, bh) tile on the TPU's (sublane, lane) grid, so round l of the pour
reads a whole tile. With the rung axis last, the k <= 16 rungs would sit
on the 128-wide lane axis (8x-16x lane padding in VMEM) and every round
would slice lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import compiler_params


def pour_entry_costs(x, zg, wg, iters: int):
    """Per-entry poured cost of the k-round water-filling ladder.
    x (bn, bh); zg (iters+1, bn, bh); wg (iters, bn, bh) -> (bn, bh)."""
    acc = jnp.zeros_like(x)
    prefix = jnp.zeros_like(x)
    poured = jnp.zeros_like(x)
    for l in range(iters):
        w_l = wg[l].astype(jnp.float32)                      # (bn, bh)
        z_l = zg[l].astype(jnp.float32)
        r = jnp.clip(x - prefix, 0.0, w_l)
        acc = acc + r * z_l
        poured = poured + r
        prefix = prefix + w_l
    remainder = jnp.maximum(x - poured, 0.0)
    return acc + remainder * zg[iters].astype(jnp.float32)


def _act_phase2_kernel(x_ref, zg_ref, wg_ref, t_ref, *, iters: int):
    """Grid = (nq, n_blocks, h_blocks); the query batch is the outermost
    (parallel) axis and h blocks accumulate into t. The x block is shared
    across queries."""
    j = pl.program_id(2)

    x = x_ref[...].astype(jnp.float32)                       # (bn, bh)
    acc = pour_entry_costs(x, zg_ref[0], wg_ref[0], iters)
    partial = jnp.sum(acc, axis=1, keepdims=True)[None]      # (1, bn, 1)

    @pl.when(j == 0)
    def _init():
        t_ref[...] = partial

    @pl.when(j > 0)
    def _accum():
        t_ref[...] = t_ref[...] + partial


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_h", "interpret"))
def act_phase2_pallas(x: jax.Array, zg: jax.Array, wg: jax.Array, *,
                      block_n: int = 256, block_h: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Fused Phase-2 pour + Phase-3 dump over a query batch.

    Args:
      x:  (n, hmax) residual database weights, shared by all queries
          (padding slots are 0).
      zg: (nq, iters+1, n, hmax) per-query ascending transport-cost ladder.
      wg: (nq, iters, n, hmax) per-query capacity ladder (query weights).
    Returns t: (nq, n, 1) transport-cost lower bounds.
    Caller guarantees n % block_n == 0 and hmax % block_h == 0 (see ops.py).
    """
    n, hmax = x.shape
    nq, iters = wg.shape[0], wg.shape[1]
    assert zg.shape == (nq, iters + 1, n, hmax), (zg.shape, x.shape, iters)
    assert n % block_n == 0 and hmax % block_h == 0, (n, hmax, block_n, block_h)
    grid = (nq, n // block_n, hmax // block_h)
    kernel = functools.partial(_act_phase2_kernel, iters=iters)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_h), lambda q, i, j: (i, j)),
            pl.BlockSpec((1, iters + 1, block_n, block_h),
                         lambda q, i, j: (q, 0, i, j)),
            pl.BlockSpec((1, iters, block_n, block_h),
                         lambda q, i, j: (q, 0, i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n, 1), lambda q, i, j: (q, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, n, 1), jnp.float32),
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(x, zg, wg)
