"""Hierarchical k-means tree source with triangle-inequality pruning.

The data-dependent cluster-tree idea of Ding et al. 2020
(arXiv:2002.12354) applied to WCD centroids: a ``branching``-ary tree of
``depth`` levels is fit by recursive k-means at build time; each node
stores its center and its RADIUS (max member distance), so at query
time ``max(d(q, center) - radius, 0)`` triangle-inequality lower-bounds
the distance to EVERY row under the node — the pruning signal a beam
descent keeps the ``beam`` most promising nodes by.

The tree is flattened to fixed-depth arrays (heap-layout node table,
one dense leaf-row table), so the whole descent is a ``lax.scan`` over
levels of fixed-shape gathers: jittable, query-batched, mesh-shardable,
and touching ``beam * branching`` nodes per level plus
``probes * leaf_cap`` leaf rows — never the corpus.

Not admissible (a pruned subtree can hide a true neighbor), so sourced
cascades report measured recall.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.candidates.base import (EMPTY_CENTER, SourceSpec,
                                   corpus_centroids, kmeans, pack_table,
                                   refine_by_centroid, register_source,
                                   slot_centroids)
from repro.core import lc
from repro.core.geometry import weighted_centroids


def _level_offset(branching: int, level: int) -> int:
    """Start index of 1-indexed ``level`` in the heap-flat node table
    (levels 1..depth stored contiguously; the root is implicit)."""
    return sum(branching ** j for j in range(1, level))


@register_source
@dataclasses.dataclass(frozen=True)
class ClusterTreeSpec(SourceSpec):
    """Build parameters of the cluster tree.

    branching/depth: tree shape — ``branching ** depth`` leaves.
    beam:            nodes kept per level during descent (<= branching,
                     so the frontier width is constant across levels).
    probes:          leaves whose rows are gathered (<= beam).
    leaf_cap:        rows kept per leaf; ``None`` = fullest leaf
                     (lossless; static checkers need an explicit cap).
    refine:          optional exact-WCD refine: keep only the ``refine``
                     centroid-nearest of the probed leaf rows (see
                     ``CentroidLSHSpec.refine``).
    kmeans_iters/seed: per-node k-means fitting knobs.
    """

    kind = "cluster_tree"
    admissible = False
    full_scan = False

    branching: int = 8
    depth: int = 2
    beam: int = 4
    probes: int = 4
    leaf_cap: int | None = None
    refine: int | None = None
    kmeans_iters: int = 4
    seed: int = 0

    def __post_init__(self) -> None:
        if self.branching < 2 or self.depth < 1:
            raise ValueError("need branching >= 2 and depth >= 1, got "
                             f"{self.branching}/{self.depth}")
        if not 1 <= self.beam <= self.branching:
            raise ValueError(
                f"beam must be in [1, branching={self.branching}] (the "
                f"descent frontier has constant width), got {self.beam}")
        if not 1 <= self.probes <= self.beam:
            raise ValueError(f"probes must be in [1, beam={self.beam}], "
                             f"got {self.probes}")
        if self.leaf_cap is not None and self.leaf_cap < 1:
            raise ValueError(f"leaf_cap must be >= 1 or None, got "
                             f"{self.leaf_cap}")
        if self.refine is not None:
            if self.refine < 1:
                raise ValueError(f"refine must be >= 1 or None, got "
                                 f"{self.refine}")
            if self.leaf_cap is not None and \
                    self.refine > self.probes * self.leaf_cap:
                raise ValueError(
                    f"refine={self.refine} exceeds the probed width "
                    f"probes*leaf_cap={self.probes * self.leaf_cap}")
        if self.kmeans_iters < 1:
            raise ValueError("kmeans_iters must be >= 1")

    @property
    def n_leaves(self) -> int:
        return self.branching ** self.depth

    @property
    def n_nodes(self) -> int:
        return _level_offset(self.branching, self.depth + 1)

    @property
    def width(self) -> int | None:
        if self.refine is not None:
            return self.refine
        return None if self.leaf_cap is None \
            else self.probes * self.leaf_cap

    def build(self, corpus, *, n_valid: int | None = None):
        """Recursive k-means over the row centroids, flattened level by
        level; radii are exact member maxima, so the descent's
        triangle-inequality bound is sound by construction."""
        rng = np.random.default_rng(self.seed)
        x = corpus_centroids(corpus, n_valid=n_valid)
        B = self.branching
        nodes = np.full((self.n_nodes, x.shape[1]), EMPTY_CENTER,
                        np.float32)
        radii = np.zeros(self.n_nodes, np.float32)
        parent = np.zeros(x.shape[0], np.int64)
        for level in range(1, self.depth + 1):
            off = _level_offset(B, level)
            child = np.zeros(x.shape[0], np.int64)
            for p in range(B ** (level - 1)):
                member = np.nonzero(parent == p)[0]
                if member.size == 0:
                    continue                 # whole subtree stays empty
                c, a = kmeans(x[member], B, self.kmeans_iters, rng)
                counts = np.bincount(a, minlength=B)
                c[counts == 0] = EMPTY_CENTER
                nodes[off + p * B:off + (p + 1) * B] = c
                child[member] = p * B + a
                dist = np.linalg.norm(x[member] - c[a], axis=1)
                np.maximum.at(radii, off + p * B + a, dist)
            parent = child
        rows, mask, dropped = pack_table(parent, self.n_leaves,
                                         self.leaf_cap)
        if self.refine is not None and \
                self.refine > self.probes * rows.shape[1]:
            raise ValueError(
                f"refine={self.refine} exceeds the probed width "
                f"probes*cap={self.probes * rows.shape[1]} of the built "
                "table")
        cents = slot_centroids(x, rows, mask) \
            if self.refine is not None else None
        return ClusterTreeSource(
            spec=self, nodes=jnp.asarray(nodes), radii=jnp.asarray(radii),
            rows=jnp.asarray(rows), mask=jnp.asarray(mask),
            cents=None if cents is None else jnp.asarray(cents),
            dropped_rows=dropped)

    def state_structs(self, m: int) -> tuple:
        if self.leaf_cap is None:
            raise ValueError(
                "leaf_cap=None sizes the leaf table to the data; the "
                "static checkers need an explicit leaf_cap to know the "
                "state shapes without building")
        out = (jax.ShapeDtypeStruct((self.n_nodes, m), jnp.float32),
               jax.ShapeDtypeStruct((self.n_nodes,), jnp.float32),
               jax.ShapeDtypeStruct((self.n_leaves, self.leaf_cap),
                                    jnp.int32),
               jax.ShapeDtypeStruct((self.n_leaves, self.leaf_cap),
                                    jnp.bool_))
        if self.refine is not None:
            out += (jax.ShapeDtypeStruct(
                (self.n_leaves, self.leaf_cap, m), jnp.float32),)
        return out

    def wrap(self, leaves):
        if self.refine is not None:
            nodes, radii, rows, mask, cents = leaves
        else:
            (nodes, radii, rows, mask), cents = leaves, None
        return ClusterTreeSource(spec=self, nodes=nodes, radii=radii,
                                 rows=rows, mask=mask, cents=cents)

    def describe(self) -> str:
        cap = "max" if self.leaf_cap is None else self.leaf_cap
        ref = "" if self.refine is None else f" r{self.refine}"
        return (f"cluster_tree[b{self.branching}^d{self.depth} "
                f"beam{self.beam} p{self.probes} cap{cap}{ref}]")


@dataclasses.dataclass(frozen=True)
class ClusterTreeSource:
    """Built tree: heap-flat node centers/radii + dense leaf-row table.
    Registered as a jax pytree (spec static)."""

    spec: ClusterTreeSpec
    nodes: jax.Array                    # (n_nodes, m) float32 centers
    radii: jax.Array                    # (n_nodes,) float32 max member dist
    rows: jax.Array                     # (n_leaves, cap) int32 row ids
    mask: jax.Array                     # (n_leaves, cap) validity
    cents: jax.Array | None = None      # (n_leaves, cap, m) refine table
    dropped_rows: int = 0

    @property
    def width(self) -> int:
        if self.spec.refine is not None:
            return self.spec.refine
        return self.spec.probes * self.rows.shape[1]

    def _bound(self, qc, node_ids):
        """Triangle-inequality descent key: ``d(q, center) - radius``.
        Clamped at zero it is a true lower bound on the centroid distance
        from the query to ANY row under the node (the admissible-pruning
        property the tests verify); the beam ranks by the UNCLAMPED
        value so overlapping balls (query inside several nodes' radii,
        where every clamped bound ties at 0) still order by how deep
        inside each ball the query sits."""
        cc = self.nodes[node_ids]
        d = jnp.linalg.norm(cc - qc[:, None, :], axis=-1)
        # EMPTY_CENTER distances overflow to +inf, which breaks the
        # min-extraction top-k (it masks winners to PAD_DIST < inf and
        # would re-pick them — duplicate beam slots). Clamp BELOW
        # PAD_DIST so empty subtrees still rank last but stay distinct.
        d = jnp.minimum(d, 0.5 * lc.PAD_DIST)
        return d - self.radii[node_ids]

    def candidates(self, corpus, q_ids, q_w, budget: int | None = None):
        """Beam descent as a ``lax.scan`` over levels, then a gather of
        the ``probes`` best leaves' rows. ``budget`` truncates to the
        best-ranked columns."""
        spec, B = self.spec, self.spec.branching
        qc = weighted_centroids(q_w, corpus.coords[q_ids])
        nq = q_ids.shape[0]
        # Level 1: score all B children of the (implicit) root.
        lb = self._bound(qc, jnp.broadcast_to(
            jnp.arange(B, dtype=jnp.int32)[None, :], (nq, B)))
        _, sel = lc.streaming_smallest_k(lb, spec.beam)
        ids = sel.astype(jnp.int32)          # absolute: level-1 offset is 0

        def descend(ids, offs):
            rel = ids - offs[0]
            child = (offs[1] + rel[:, :, None] * B
                     + jnp.arange(B, dtype=jnp.int32)).reshape(nq, -1)
            lb = self._bound(qc, child)
            _, pos = lc.streaming_smallest_k(lb, spec.beam)
            return jnp.take_along_axis(child, pos, axis=-1), None

        if spec.depth > 1:
            offs = jnp.asarray(
                [[_level_offset(B, lv - 1), _level_offset(B, lv)]
                 for lv in range(2, spec.depth + 1)], jnp.int32)
            ids, _ = jax.lax.scan(descend, ids, offs)
        leaf = ids - _level_offset(B, spec.depth)   # ascending-bound order
        leaf = leaf[:, :spec.probes]
        rows = self.rows[leaf].reshape(nq, -1)
        mask = self.mask[leaf].reshape(nq, -1)
        if spec.refine is not None:
            cents = self.cents[leaf].reshape(nq, rows.shape[1], -1)
            rows, mask = refine_by_centroid(qc, rows, mask, cents,
                                            spec.refine)
        if budget is not None and budget < rows.shape[1]:
            rows, mask = rows[:, :budget], mask[:, :budget]
        return rows, mask


jax.tree_util.register_dataclass(
    ClusterTreeSource,
    data_fields=["nodes", "radii", "rows", "mask", "cents"],
    meta_fields=["spec", "dropped_rows"])
