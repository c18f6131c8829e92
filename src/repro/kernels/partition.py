"""shard_map partitioning shims: the fused Pallas kernels on the mesh.

Compiled (non-interpret) ``pallas_call`` has no SPMD partitioning rule,
so before these shims the distributed backend could only run the kernels
in interpret mode (where they lower to plain HLO and shard like any jnp
op) — ``EmdIndex`` kept ``use_kernels`` off on the mesh entirely. Each
shim here wraps one kernel wrapper from :mod:`repro.kernels.ops` in an
explicit ``shard_map``: the partitioning is stated once, per kernel, as
(in_specs, out_specs), and the body runs the unmodified single-device
wrapper on its shard — compiled on a real TPU mesh, interpreted on the
host-mesh CI conformance oracle, identical program structure either way.

The mesh is threaded EXPLICITLY (a hashable static argument on every
engine down from ``launch/search.py``), never read from ambient context:
the lc engines are inner ``jax.jit``s, and a context read at trace time
would not participate in their cache keys — two meshes would silently
share one trace.

Partitioning per kernel family:

* ``dist_topk`` (Phase 1) — queries over DP, vocabulary rows over
  "model" (padded to a multiple of the axis; the pad rows are sliced off
  the ladders, no corpus id reaches them). Each (vocab-shard,
  query-shard) cell computes its own distance tile and per-row top-k;
  the selection indexes the query's histogram slots (h, unsharded), so
  the per-row result never crosses shards. The W capacity gather runs inside the shard (``Q_w`` is
  DP-local, S indexes h). Downstream, the caller re-pins the (nq, v, k)
  ladders to the ``annotate.emd_ladder`` layout — the same replication
  all-gather the jnp pipeline performs.
* ``act_phase2`` (Phase 2/3) — database rows over "model", queries over
  DP. The body gathers its row shard's (bq, n/shard, hmax, k) ladders
  and pours; the per-shard query blocking (``lc._map_query_blocks``)
  runs INSIDE the shard, so the ``lax.map`` iterates a shard-LOCAL query
  axis — XLA's SPMD partitioner cannot iterate a scan over a DP-sharded
  axis, which is why the distributed query blocking lives here and not
  above the shard_map.
* candidate kernels (``cand_pour``/``cand_omr``/``cand_rev_min``/
  ``cand_ict``) — queries over DP only. The candidate sub-corpus gather
  (``corpus.ids[cand]``) stays OUTSIDE the shard_map on purpose: inside,
  the model-sharded corpus rows would have to replicate (an O(n * hmax)
  all-gather — exactly what the static collective checker's corpus-
  scaling guard forbids), while outside, XLA's partitioned gather moves
  only the (nq, b, hmax) candidate rows. The model axis is unmentioned
  in the specs: inputs are replicated over it and every model shard
  computes the same (nq/dp, b) block (``check_vma=False`` skips the
  replication proof current shard_map cannot do for these bodies).

Every shim has a divisibility precondition (``queries_shardable`` and
``rows_shardable``); callers fall back to the non-shard_map kernel path
when a dim does not split. That fallback is an interpret-mode (host
mesh) path only: the TPU compiler refuses a Mosaic kernel it would have
to partition itself, so on a chip mesh it fails loudly instead.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import lc, scopes
from repro.kernels import ops as kops
from repro.launch.mesh import data_axes, model_axis_size

_shard_map = functools.partial(jax.shard_map, check_vma=False)


def _dp(mesh):
    """The mesh's DP axes as one PartitionSpec entry."""
    axes = data_axes(mesh)
    return axes if len(axes) > 1 else axes[0]


def _dp_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))


def _handoff_layout(mesh, a):
    """Pin a (nq, v, k) Phase-1 handoff to the ``annotate.emd_ladder``
    layout — queries on their DP shards, the rest replicated — on the
    explicit ``mesh``, which the engines may reach without an ambient
    one. Left vocabulary-sharded, its consumers' gathers would partition
    into per-shard partial sums. Reduced-precision ladders cross as
    same-width unsigned integers, as in ``emd_ladder``."""
    sh = NamedSharding(mesh, P(_dp(mesh), *([None] * (a.ndim - 1))))
    if a.dtype == jnp.float32:
        return jax.lax.with_sharding_constraint(a, sh)
    u = jax.lax.bitcast_convert_type(
        a, jnp.dtype(f"uint{a.dtype.itemsize * 8}"))
    return jax.lax.bitcast_convert_type(
        jax.lax.with_sharding_constraint(u, sh), a.dtype)


def queries_shardable(mesh, nq: int) -> bool:
    """True when the query batch splits evenly over the mesh's DP axes —
    the precondition of every shim here."""
    return nq % _dp_size(mesh) == 0


def rows_shardable(mesh, nq: int, n: int) -> bool:
    """Precondition of :func:`act_pour_sharded`: queries split over DP
    and database rows over "model"."""
    return queries_shardable(mesh, nq) and n % model_axis_size(mesh) == 0


def dist_topk_sharded(mesh, coords, qcs, Q_w, k: int, *,
                      block_v: int = 256, block_h: int = 256,
                      out_dtype: str = "float32"):
    """Phase-1 kernel on the mesh: coords (v, m) sharded over "model",
    qcs (nq, h, m) / Q_w (nq, h) over DP -> Z (nq, v, k) and the
    capacities of its first k-1 rungs W (nq, v, k-1) — the last rung
    takes only the Phase-3 dump, whose capacity no reduction reads — in
    ``out_dtype`` (a precision policy's storage role — this is the
    handoff whose replication all-gather the policy halves), on the
    emd_ladder layout. Precondition: :func:`queries_shardable`."""
    v = coords.shape[0]
    coords = jnp.pad(coords, ((0, -v % model_axis_size(mesh)), (0, 0)))
    def body(coords_l, qcs_l, qw_l):
        Z, S = kops.dist_topk_batched(coords_l, qcs_l, k,
                                      qmask=(qw_l > 0.0), block_v=block_v,
                                      block_h=block_h, out_dtype=out_dtype)
        W = lc.take_bins(qw_l, S[..., :k - 1]).astype(out_dtype)
        return Z, W

    dp = _dp(mesh)
    Z, W = _shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None), P(dp, None, None), P(dp, None)),
        out_specs=(P(dp, "model", None), P(dp, "model", None)),
    )(coords, qcs, Q_w)
    return _handoff_layout(mesh, Z[:, :v]), _handoff_layout(mesh, W[:, :v])


def act_pour_sharded(mesh, ids, w, Z, W, iters: int, *, block_q: int = 8,
                     block_n: int = 256, block_h: int = 256):
    """Phase-2/3 kernel on the mesh: corpus ids/w (n, hmax) sharded over
    "model", handoff ladders Z (nq, v, iters+1) / W (nq, v, iters) over
    DP (replicated over "model" — the emd_ladder layout) -> (nq, n)
    scores on the (DP, "model") grid. ``iters >= 1`` (the zero-round dump
    has no kernel form). Query blocking runs per shard.

    Reduced-precision ladders (a policy's bf16 storage) cross the
    shard_map boundary BITCAST to a same-width unsigned integer and come
    back to their float dtype inside the shard: the in_specs replication
    all-gather otherwise runs on a float value XLA rewrites to f32 width
    (see ``annotate.emd_ladder``), doubling the handoff wire bytes the
    policy exists to halve."""
    assert iters >= 1, iters
    zdt, wdt = Z.dtype, W.dtype

    def _fence(a):
        if a.dtype == jax.numpy.float32:
            return a
        return jax.lax.bitcast_convert_type(
            a, jax.numpy.dtype(f"uint{a.dtype.itemsize * 8}"))

    def body(ids_l, w_l, Z_l, W_l):
        Z_l = (Z_l if Z_l.dtype == zdt
               else jax.lax.bitcast_convert_type(Z_l, zdt))
        W_l = (W_l if W_l.dtype == wdt
               else jax.lax.bitcast_convert_type(W_l, wdt))

        def blk(Zb, Wb):
            with jax.named_scope(scopes.LADDER_GATHER):
                Zg = jnp.swapaxes(Zb, 1, 2)[:, :, ids_l]  # (bq, k, n/sh, hmax)
                Wg = jnp.swapaxes(Wb, 1, 2)[:, :, ids_l]
            return kops.act_phase2_batched(w_l, Zg, Wg, block_n=block_n,
                                           block_h=block_h)
        return lc._map_query_blocks(blk, (Z_l, W_l), Z_l.shape[0], block_q)

    dp = _dp(mesh)
    return _shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None), P("model", None),
                  P(dp, None, None), P(dp, None, None)),
        out_specs=P(dp, "model"),
    )(ids, w, _fence(Z), _fence(W))


def cand_sharded(mesh, fn, arrays, block_q: int = 8):
    """Candidate kernel on the mesh: every array in ``arrays`` leads with
    the query axis and shards over DP (trailing dims replicated); ``fn``
    maps the per-block slices to (bq, b) scores and runs inside the shard
    under per-shard query blocking. The candidate gather must already
    have happened OUTSIDE (see the module docstring)."""
    def body(*local):
        return lc._map_query_blocks(fn, local, local[0].shape[0], block_q)

    dp = _dp(mesh)
    in_specs = tuple(P(dp, *([None] * (a.ndim - 1))) for a in arrays)
    return _shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=P(dp, None))(*arrays)
