"""Plain reference for the EMD search deployments, in float32.

Written from the paper's definitions, in straightforward ``jax.numpy``,
importing nothing of the program under test:

* ground distance: Euclidean distance between vocabulary coordinates,
  taken as 0 where its square falls below ``zero_snap**2 * (|a|^2 +
  |b|^2)`` (the configuration's ground-distance rule: identical
  coordinates cost exactly 0);
* LC-ACT-k (Atasu & Mittelholzer, Algorithm 3): for every vocabulary
  word, the k = iters + 1 nearest query bins and their weights; each
  database entry pours its mass into them in ``iters`` sequential rounds
  of ``min``/subtract and dumps the rest at the k-th cost;
* LC-RWMD: the same with no rounds (every entry at its nearest bin);
* WCD: distance between weighted centroids;
* a cascade: keep the best rows of each stage among the previous
  stage's survivors, rescore the last survivors, take the top l. Ties
  go to the lowest row id everywhere;
* a cascade fed by a candidate source: the exact top l of its
  rescorer's measure over the whole corpus (the source is approximate by
  design; its answers are judged by recall against this).

Every float32 matrix product runs in ``passes`` bfloat16 passes: 6 is
float32 at ``highest`` precision (the configurations' own), 3 is
``high`` (the control), 1 is one bfloat16 pass. ``storage="bfloat16"``
rounds the Phase-1 cost and capacity ladders to bfloat16 (the other
control). Work runs one query at a time and in blocks of rows, so that
it fits a chip beside nothing else. A corpus whose rows lie over several
chips is scored chip by chip, each chip its own rows, and the scores
joined in row order: each row's arithmetic is the one-device
reference's, so the scores are too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Cost of a padding query bin: never among the k nearest of a real bin.
BIG = 1e30


def matmul_t(a, b, passes: int):
    """a (p, m) @ b (q, m).T in float32 from ``passes`` bfloat16 passes."""
    if passes == 6:
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)

    def dot(x, y):
        return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    a_hi, b_hi = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    if passes == 1:
        return dot(a_hi, b_hi)
    if passes != 3:
        raise ValueError(f"passes must be 1, 3 or 6, got {passes}")
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def ground_dist(a, b, snap: float, passes: int):
    """(p, q) Euclidean distances between the rows of a and b."""
    a2 = jnp.sum(a * a, axis=1)[:, None]
    b2 = jnp.sum(b * b, axis=1)[None, :]
    d2 = jnp.maximum(a2 + b2 - 2.0 * matmul_t(a, b, passes), 0.0)
    d2 = jnp.where(d2 < snap * snap * (a2 + b2), 0.0, d2)
    return jnp.sqrt(d2)


def _round(x, storage: str):
    if storage == "float32":
        return x
    return x.astype(jnp.dtype(storage)).astype(jnp.float32)


def ladders(coords, q_ids, q_w, k: int, snap: float, passes: int,
            storage: str):
    """(v, k) ascending costs of each word's k nearest query bins, and
    the query weights at those bins."""
    d = ground_dist(coords, coords[q_ids], snap, passes)     # (v, h)
    d = jnp.where(q_w[None, :] > 0.0, d, BIG)
    neg, pos = jax.lax.top_k(-d, k)
    return _round(-neg, storage), _round(q_w[pos], storage)


def pour_rows(x, z, c, iters: int):
    """Per-row cost of pouring weights x (b, h) through cost ladders
    z (b, h, k) with capacities c (b, h, k): ``iters`` rounds, then the
    rest at z[..., iters]."""
    t = jnp.zeros_like(x)
    for r in range(iters):
        y = jnp.minimum(x, c[..., r])
        t = t + y * z[..., r]
        x = x - y
    t = t + x * z[..., iters]
    return jnp.sum(t, axis=1)


def _row_blocks(n: int, rows: int):
    return -(-n // rows)


@functools.partial(jax.jit, static_argnames=("iters", "passes", "storage",
                                             "rows"))
def act_scores(ids, w, coords, q_ids, q_w, *, iters: int, snap: float,
               passes: int = 6, storage: str = "float32", rows: int = 4096):
    """(n,) LC-ACT-``iters`` costs of moving every row into the query
    (iters = 0: LC-RWMD)."""
    z, c = ladders(coords, q_ids, q_w, iters + 1, snap, passes, storage)
    n, h = ids.shape
    nb = _row_blocks(n, rows)
    pad = nb * rows - n
    ids_b = jnp.pad(ids, ((0, pad), (0, 0))).reshape(nb, rows, h)
    w_b = jnp.pad(w, ((0, pad), (0, 0))).reshape(nb, rows, h)

    def block(args):
        i, x = args
        return pour_rows(x, z[i], c[i], iters)

    return jax.lax.map(block, (ids_b, w_b)).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("passes", "rows"))
def centroids(ids, w, coords, *, passes: int = 6, rows: int = 1024):
    """(n, m) weighted centroids of the rows."""
    n, h = ids.shape
    nb = _row_blocks(n, rows)
    pad = nb * rows - n
    ids_b = jnp.pad(ids, ((0, pad), (0, 0))).reshape(nb, rows, h)
    w_b = jnp.pad(w, ((0, pad), (0, 0))).reshape(nb, rows, h)
    prec = (jax.lax.Precision.HIGHEST if passes == 6
            else jax.lax.Precision.HIGH if passes == 3
            else jax.lax.Precision.DEFAULT)

    def block(args):
        i, x = args
        return jnp.einsum("bh,bhm->bm", x, coords[i], precision=prec)

    return jax.lax.map(block, (ids_b, w_b)).reshape(-1, coords.shape[1])[:n]


def wcd_scores(cent, qcent):
    """(n,) distances between row centroids and the query's."""
    d = cent - qcent[None, :]
    return jnp.sqrt(jnp.sum(d * d, axis=1))


def topl(scores, l: int, allowed=None):
    """(values, rows) of the l smallest scores, ascending; ties to the
    lowest row. ``allowed`` masks rows out of candidacy."""
    if allowed is not None:
        scores = jnp.where(allowed, scores, jnp.inf)
    neg, idx = jax.lax.top_k(-scores, l)
    return -neg, idx


def budgets(stages, n: int, top_l: int) -> list[int]:
    """Rows kept by each stage: a fraction of n (rounded) or a count,
    clamped to [top_l, previous stage's]."""
    out, prev = [], n
    for stage in stages:
        b = stage[1]
        k = int(round(b * n)) if isinstance(b, float) else int(b)
        k = max(min(k, prev), top_l)
        out.append(k)
        prev = k
    return out


def chip_blocks(ids, w, coords) -> list[tuple]:
    """(ids, w, coords) of each chip's own rows, in row order: the whole
    corpus where it lies on one device; else each chip's block of the
    row-sharded corpus, beside its own copy of ``coords``."""
    if len(ids.sharding.device_set) == 1:
        return [(ids, w, coords)]

    def by_start(x):
        return {s.index[0].start or 0: s.data
                for s in sorted(x.addressable_shards,
                                key=lambda s: s.device.id)}
    ids_at, w_at = by_start(ids), by_start(w)
    blocks = []
    for start in sorted(ids_at):
        dev = next(iter(ids_at[start].devices()))
        blocks.append((ids_at[start], jax.device_put(w_at[start], dev),
                       jax.device_put(coords, dev)))
    return blocks


class Reference:
    """The reference search of one deployment under one engine setting.

    ``engine`` holds the search as the traffic states it: ``method`` and
    ``iters`` for a full-corpus search, or ``stages`` (a list of
    ``[method, budget]``) and ``rescorer`` (``[method, iters]``) for a
    cascade, and ``source`` where a candidate source feeds it."""

    def __init__(self, ids, w, coords, engine: dict, snap: float, *,
                 passes: int = 6, storage: str = "float32"):
        self.blocks = chip_blocks(ids, w, coords)
        self.n = ids.shape[0]
        self.engine, self.snap = engine, snap
        self.passes, self.storage = passes, storage
        self.top_l = engine["top_l"]
        self._cent = None

    def _block_score(self, b: int, method: str, iters: int, q_ids, q_w):
        ids, w, coords = self.blocks[b]
        if method in ("act", "rwmd"):
            return act_scores(ids, w, coords, q_ids, q_w,
                              iters=iters if method == "act" else 0,
                              snap=self.snap, passes=self.passes,
                              storage=self.storage)
        if method == "wcd":
            if self._cent is None:
                self._cent = [centroids(i, x, c, passes=self.passes)
                              for i, x, c in self.blocks]
            qc = centroids(q_ids[None], q_w[None], coords,
                           passes=self.passes, rows=1)[0]
            return wcd_scores(self._cent[b], qc)
        raise ValueError(f"the reference has no method {method!r}")

    def _score(self, method: str, iters: int, q_ids, q_w):
        """(n,) scores of every row: each chip scores its own rows, all
        chips dispatched before any result is read."""
        if len(self.blocks) == 1:
            return self._block_score(0, method, iters, q_ids, q_w)
        parts = []
        for b, (ids, _, _) in enumerate(self.blocks):
            dev = next(iter(ids.devices()))
            parts.append(self._block_score(
                b, method, iters, jax.device_put(q_ids, dev),
                jax.device_put(q_w, dev)))
        return jnp.asarray(np.concatenate([np.asarray(p) for p in parts]))

    def query(self, q_ids, q_w):
        """(final scores of every row, top-l values, top-l rows) of one
        query: the final scores are the measure the top l is ranked by
        (the rescorer's, for a cascade). A sourced cascade's top l is the
        rescorer's over every row."""
        e = self.engine
        n = self.n
        if "stages" not in e or "source" in e:
            method, iters = ((e["method"], e["iters"]) if "stages" not in e
                             else e["rescorer"])
            s = self._score(method, iters, q_ids, q_w)
            vals, rows = topl(s, self.top_l)
            return s, vals, rows
        allowed = None
        for stage, keep in zip(e["stages"],
                               budgets(e["stages"], n, self.top_l)):
            iters = stage[2] if len(stage) > 2 else 1
            s = self._score(stage[0], iters, q_ids, q_w)
            _, rows = topl(s, keep, allowed)
            allowed = jnp.zeros((n,), bool).at[rows].set(True)
        method, iters = e["rescorer"]
        s = self._score(method, iters, q_ids, q_w)
        vals, rows = topl(s, self.top_l, allowed)
        return s, vals, rows

    def answers(self, q_ids, q_w):
        """Top-l (values, rows) of a batch of queries, as numpy."""
        out = [self.query(q_ids[i], q_w[i]) for i in range(q_ids.shape[0])]
        return (np.stack([np.asarray(o[1]) for o in out]),
                np.stack([np.asarray(o[2]) for o in out]))
