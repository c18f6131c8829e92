"""IVF/LSH candidate source over WCD centroids.

The paper's WCD baseline is already the cascade's cheap prefetch; this
source moves it BELOW linear: at build time every corpus row's weighted
centroid is quantized into one of ``n_buckets`` coarse cells (a k-means
codebook — classic IVF — or random hyperplane signs — classic LSH), and
the rows of each cell are packed into a dense ``(n_buckets, cap)``
table. At query time the step computes the query centroids, ranks the
bucket centroids (an ``(nq, n_buckets)`` matmul — buckets, not rows),
and gathers the rows of the ``probes`` nearest buckets: every op is a
dense matmul/gather over fixed-width tables, so the step jits, batches
over queries, and shards on the mesh with traffic proportional to
``probes * cap`` PROBED rows — never to the corpus. This is the
nearest-neighbor-search EMD approximation pattern of Meng et al. 2024
(arXiv:2401.07378) specialized to the WCD embedding the repo already
trusts as its prefetch heuristic.

Not admissible: a true neighbor whose bucket is not probed is lost, so
cascades sourced here always report MEASURED recall.
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


@register_source
@dataclasses.dataclass(frozen=True)
class CentroidLSHSpec(SourceSpec):
    """Build parameters of the coarse centroid quantizer.

    quantizer:   ``kmeans`` (IVF codebook, data-dependent) or
                 ``hyperplane`` (sign-pattern LSH, data-independent;
                 ``n_buckets`` must then be a power of two — one bit
                 per hyperplane).
    n_buckets:   coarse cells. More cells = finer probes; sqrt(n)-ish
                 is the usual IVF operating point.
    probes:      buckets gathered per query, nearest centroid first.
    bucket_cap:  rows kept per bucket; ``None`` sizes the table to the
                 fullest bucket (lossless, data-dependent shape — the
                 static checkers need an explicit cap), an int drops
                 overflow beyond it.
    refine:      optional exact-WCD refine: the source stores per-slot
                 row centroids and returns only the ``refine``
                 centroid-nearest of the probed rows (classic IVF-flat).
                 This IS the reference cascade's full-scan WCD stage
                 restricted to probed rows — without it, probed rows
                 outside the reference's WCD prefix crowd true
                 neighbors out of the next stage's budget.
    kmeans_iters/seed: quantizer fitting knobs.
    """

    kind = "centroid_lsh"
    admissible = False
    full_scan = False

    quantizer: str = "kmeans"
    n_buckets: int = 64
    probes: int = 8
    bucket_cap: int | None = None
    refine: int | None = None
    kmeans_iters: int = 4
    seed: int = 0

    def __post_init__(self) -> None:
        if self.quantizer not in ("kmeans", "hyperplane"):
            raise ValueError(f"unknown quantizer {self.quantizer!r}; "
                             "one of ('kmeans', 'hyperplane')")
        if self.n_buckets < 2 or self.probes < 1:
            raise ValueError("need n_buckets >= 2 and probes >= 1, got "
                             f"{self.n_buckets}/{self.probes}")
        if self.probes > self.n_buckets:
            raise ValueError(f"probes={self.probes} exceeds "
                             f"n_buckets={self.n_buckets}")
        if self.quantizer == "hyperplane" and \
                self.n_buckets & (self.n_buckets - 1):
            raise ValueError("hyperplane LSH needs a power-of-two "
                             f"n_buckets (one sign bit per plane), got "
                             f"{self.n_buckets}")
        if self.bucket_cap is not None and self.bucket_cap < 1:
            raise ValueError(f"bucket_cap must be >= 1 or None, got "
                             f"{self.bucket_cap}")
        if self.refine is not None:
            if self.refine < 1:
                raise ValueError(f"refine must be >= 1 or None, got "
                                 f"{self.refine}")
            if self.bucket_cap is not None and \
                    self.refine > self.probes * self.bucket_cap:
                raise ValueError(
                    f"refine={self.refine} exceeds the probed width "
                    f"probes*bucket_cap={self.probes * self.bucket_cap}")
        if self.kmeans_iters < 1:
            raise ValueError("kmeans_iters must be >= 1")

    @property
    def width(self) -> int | None:
        """Candidate columns the built source emits per query, when
        statically known (``None`` = known only after build)."""
        if self.refine is not None:
            return self.refine
        return None if self.bucket_cap is None \
            else self.probes * self.bucket_cap

    def build(self, corpus, *, n_valid: int | None = None):
        """Quantize the (real) corpus rows' centroids and pack the
        bucket table — host-side numpy, once, at ``EmdIndex.build``."""
        rng = np.random.default_rng(self.seed)
        x = corpus_centroids(corpus, n_valid=n_valid)
        if self.quantizer == "kmeans":
            centers, assign = kmeans(x, self.n_buckets, self.kmeans_iters,
                                     rng)
        else:
            nbits = self.n_buckets.bit_length() - 1
            planes = rng.standard_normal((nbits,
                                          x.shape[1])).astype(np.float32)
            bits = (x @ planes.T) > 0.0
            assign = bits @ (1 << np.arange(nbits, dtype=np.int64))
            centers = np.full((self.n_buckets, x.shape[1]), EMPTY_CENTER,
                              np.float32)
        rows, mask, dropped = pack_table(assign, self.n_buckets,
                                         self.bucket_cap)
        # Empirical bucket centroids (the probe targets) for BOTH
        # quantizers: hyperplane cells are ranked by where their members
        # actually sit, and empty cells keep the far sentinel so they
        # are probed last.
        counts = np.bincount(assign, minlength=self.n_buckets)
        sums = np.empty((self.n_buckets, x.shape[1]), np.float64)
        for j in range(x.shape[1]):
            sums[:, j] = np.bincount(assign, weights=x[:, j],
                                     minlength=self.n_buckets)
        live = counts > 0
        centers[live] = (sums[live] / counts[live, None]).astype(np.float32)
        centers[~live] = EMPTY_CENTER
        if self.refine is not None and \
                self.refine > self.probes * rows.shape[1]:
            raise ValueError(
                f"refine={self.refine} exceeds the probed width "
                f"probes*cap={self.probes * rows.shape[1]} of the built "
                "table")
        cents = slot_centroids(x, rows, mask) \
            if self.refine is not None else None
        return CentroidLSHSource(
            spec=self, centroids=jnp.asarray(centers),
            rows=jnp.asarray(rows), mask=jnp.asarray(mask),
            cents=None if cents is None else jnp.asarray(cents),
            dropped_rows=dropped)

    def state_structs(self, m: int) -> tuple:
        if self.bucket_cap is None:
            raise ValueError(
                "bucket_cap=None sizes the table to the data; the static "
                "checkers need an explicit bucket_cap to know the state "
                "shapes without building")
        nb, cap = self.n_buckets, self.bucket_cap
        out = (jax.ShapeDtypeStruct((nb, m), jnp.float32),
               jax.ShapeDtypeStruct((nb, cap), jnp.int32),
               jax.ShapeDtypeStruct((nb, cap), jnp.bool_))
        if self.refine is not None:
            out += (jax.ShapeDtypeStruct((nb, cap, m), jnp.float32),)
        return out

    def wrap(self, leaves):
        if self.refine is not None:
            centroids, rows, mask, cents = leaves
        else:
            (centroids, rows, mask), cents = leaves, None
        return CentroidLSHSource(spec=self, centroids=centroids,
                                 rows=rows, mask=mask, cents=cents)

    def describe(self) -> str:
        cap = "max" if self.bucket_cap is None else self.bucket_cap
        ref = "" if self.refine is None else f" r{self.refine}"
        return (f"centroid_lsh[{self.quantizer} b{self.n_buckets} "
                f"p{self.probes} cap{cap}{ref}]")


@dataclasses.dataclass(frozen=True)
class CentroidLSHSource:
    """Built IVF/LSH index: bucket centroids + dense row table. A jax
    pytree (arrays = leaves, spec = static), so it rides through jit and
    the checkpoint store unchanged."""

    spec: CentroidLSHSpec
    centroids: jax.Array                # (n_buckets, m) float32
    rows: jax.Array                     # (n_buckets, cap) int32 row ids
    mask: jax.Array                     # (n_buckets, cap) validity
    cents: jax.Array | None = None      # (n_buckets, cap, m) refine table
    dropped_rows: int = 0               # overflow beyond an explicit cap

    @property
    def width(self) -> int:
        if self.spec.refine is not None:
            return self.spec.refine
        return self.spec.probes * self.rows.shape[1]

    def candidates(self, corpus, q_ids, q_w, budget: int | None = None):
        """(nq, width) candidate row ids + validity mask — nearest probed
        bucket first, or ascending exact centroid distance under
        ``refine``; ``budget`` truncates to the best-ranked columns.
        Jittable; every shape is fixed by the spec, and the only data
        touched scales with probed rows."""
        qc = weighted_centroids(q_w, corpus.coords[q_ids])
        d = jnp.linalg.norm(self.centroids[None, :, :] - qc[:, None, :],
                            axis=-1)
        # EMPTY_CENTER distances overflow to +inf, which breaks the
        # min-extraction top-k (it masks winners to PAD_DIST < inf and
        # would re-pick them — duplicate probes). Clamp BELOW PAD_DIST
        # so empty buckets still rank last but stay distinct.
        d = jnp.minimum(d, 0.5 * lc.PAD_DIST)
        _, probe = lc.streaming_smallest_k(d, self.spec.probes)
        nq = q_ids.shape[0]
        rows = self.rows[probe].reshape(nq, -1)
        mask = self.mask[probe].reshape(nq, -1)
        if self.spec.refine is not None:
            cents = self.cents[probe].reshape(nq, rows.shape[1], -1)
            rows, mask = refine_by_centroid(qc, rows, mask, cents,
                                            self.spec.refine)
        if budget is not None and budget < rows.shape[1]:
            rows, mask = rows[:, :budget], mask[:, :budget]
        return rows, mask


jax.tree_util.register_dataclass(
    CentroidLSHSource, data_fields=["centroids", "rows", "mask", "cents"],
    meta_fields=["spec", "dropped_rows"])
