"""MNIST-like digit images, made on the device from a seed.

Each class is a fixed pattern of strokes (line segments); an image
jitters its class's stroke end points and renders them as blurred lines
on a side x side grid. Its lit pixels are the ``L`` brightest, where
``L`` comes from a fixed multiset around MNIST's mean of about 150 lit
pixels per image (listed under ``assumed``), and its weights are their
intensities, normalised. The vocabulary is the grid: coordinates are the
pixel positions (row, column), so ground distances are Euclidean
distances in pixels, as in the paper's MNIST experiments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from emd_bench.gen.common import (Data, make_rows, normal_sizes, seed_key,
                                  shuffled)


def grid_coords(side: int) -> jax.Array:
    yy, xx = jnp.meshgrid(jnp.arange(side), jnp.arange(side), indexing="ij")
    return jnp.stack([yy.ravel(), xx.ravel()], axis=1).astype(jnp.float32)


def _segment_dist2(p, a, b):
    """Squared distance of points p (v, 2) to segments a-b (s, 2) each:
    (v, s)."""
    ab = b - a                                            # (s, 2)
    ap = p[:, None, :] - a[None]                          # (v, s, 2)
    t = jnp.clip(jnp.sum(ap * ab, -1) / jnp.maximum(jnp.sum(ab * ab, -1),
                                                     1e-9), 0.0, 1.0)
    d = ap - t[..., None] * ab
    return jnp.sum(d * d, -1)


def _chunks(kj, first, protos, labels, lens, *, side, hmax, jitter, blur):
    """(rows, hmax) ids and weights of the chunks ``first``,
    ``first + 1``, ...: chunk i jitters from ``fold_in(kj, i)`` alone."""
    keys = jax.vmap(lambda i: jax.random.fold_in(kj, i))(
        first + jnp.arange(labels.shape[0]))
    grid = grid_coords(side)
    v = side * side

    def one(args):
        k, lab, ln = args
        ends = protos[lab] + jitter * jax.random.normal(
            k, protos[lab].shape)                         # (chunk, s, 2, 2)
        d2 = jax.vmap(lambda e: _segment_dist2(grid, e[:, 0], e[:, 1]))(ends)
        inten = jnp.exp(-jnp.min(d2, axis=-1) / (2 * blur * blur))  # (c, v)
        order = jnp.argsort(-inten, axis=1, stable=True)[:, :hmax]
        live = jnp.arange(hmax)[None, :] < ln[:, None]
        val = jnp.take_along_axis(inten, order, axis=1)
        val = jnp.maximum(val, 1e-3 * val[:, :1]) * live
        w = val / jnp.sum(val, axis=1, keepdims=True)
        return jnp.where(live, order, 0).astype(jnp.int32), w

    assert hmax <= v, (hmax, v)
    ids, w = jax.lax.map(one, (keys, labels, lens))
    return ids.reshape(-1, hmax), w.reshape(-1, hmax)


@functools.partial(jax.jit, static_argnames=("side", "hmax", "chunk",
                                             "mesh"))
def _images(key, protos, lengths, *, side, hmax, chunk, jitter, blur,
            mesh=None):
    """(rows, hmax) ids and weights for ``lengths.shape[0]`` images,
    ``chunk`` rows at a time; with a ``mesh``, over its chips."""
    chunks = functools.partial(_chunks, side=side, hmax=hmax, jitter=jitter,
                               blur=blur)
    return make_rows(chunks, key, protos, lengths, chunk, mesh)


def make(cfg: dict, seed: int, pool: int, mesh=None) -> Data:
    """The configuration's images and ``pool`` held-out query images;
    with a ``mesh``, the images over its ``model`` chips (the same
    rows)."""
    g = cfg["generator"]
    side, hmax, n = g["side"], cfg["hmax"], cfg["n"]
    if cfg["v"] != side * side or cfg["m"] != 2:
        raise ValueError(f"an image grid of side {side} has v={side * side}"
                         f" and m=2, not v={cfg['v']}, m={cfg['m']}")
    kp, kl, kql, kd, kq = jax.random.split(seed_key(seed), 5)
    margin = g["margin"]
    protos = jax.random.uniform(kp, (g["classes"], g["strokes"], 2, 2),
                                minval=margin, maxval=side - 1 - margin)
    lit = (g["mean_lit"], g["sd_lit"], g["min_lit"], g["max_lit"])
    doc_len = shuffled(kl, normal_sizes(n, *lit))
    q_len = shuffled(kql, normal_sizes(pool, *lit))
    kw = dict(side=side, hmax=hmax, chunk=g["chunk"], jitter=g["jitter"],
              blur=g["blur"])
    ids, w = _images(kd, protos, jnp.asarray(doc_len), mesh=mesh, **kw)
    q_ids, q_w = _images(kq, protos, jnp.asarray(q_len), **kw)
    return Data(ids=ids, w=w, coords=grid_coords(side), q_ids=q_ids,
                q_w=q_w, doc_len=doc_len, q_len=q_len)
