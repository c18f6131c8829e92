"""20 Newsgroups-like text corpus, made on the device from a seed.

A vectorised form of the repository's ``make_text_like`` (kept here so
that the yardstick cannot move): word2vec-style unit coordinates, topic
classes owning a region of the embedding, and documents of distinct
words drawn from their class's word distribution. Two departures, both
listed under ``assumed`` in the configuration file:

* a document's count of distinct words comes from a fixed multiset of
  heavy-tailed (lognormal) lengths clipped to [1, hmax], with the mean of
  the 20NEWS corpus, instead of 500 draws with replacement;
* word popularity follows Zipf's law, as natural text does, so common
  words are shared between documents as they are in a real corpus.

A document's ``L`` distinct words are the top ``L`` of its class's
Gumbel-perturbed log-probabilities (sampling without replacement), and
its weights are normalised exponential draws over those words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from emd_bench.gen.common import (Data, lognormal_sizes, make_rows, seed_key,
                                  shuffled)


@functools.partial(jax.jit, static_argnames=("v", "m", "classes"))
def _vocabulary(key, *, v, m, classes, topic_strength, zipf_s):
    kc, ka, kz = jax.random.split(key, 3)
    coords = jax.random.normal(kc, (v, m), jnp.float32)
    coords = coords / jnp.linalg.norm(coords, axis=1, keepdims=True)
    anchors = jax.random.normal(ka, (classes, m), jnp.float32)
    anchors = anchors / jnp.linalg.norm(anchors, axis=1, keepdims=True)
    sim = jnp.matmul(anchors, coords.T,
                     precision=jax.lax.Precision.HIGHEST)    # (classes, v)
    rank = jax.random.permutation(kz, v).astype(jnp.float32)
    logp = topic_strength * sim - zipf_s * jnp.log1p(rank)[None, :]
    return coords, logp


def _chunks(kd, first, logp, labels, lens, *, hmax):
    """(rows, hmax) ids and weights of the chunks ``first``,
    ``first + 1``, ...: chunk i draws from ``fold_in(kd, i)`` alone."""
    chunk = labels.shape[1]
    keys = jax.vmap(lambda i: jax.random.fold_in(kd, i))(
        first + jnp.arange(labels.shape[0]))

    def one(args):
        k, lab, ln = args
        kg, kw = jax.random.split(k)
        scores = logp[lab] + jax.random.gumbel(kg, (chunk, logp.shape[1]))
        _, top = jax.lax.top_k(scores, hmax)
        live = jnp.arange(hmax)[None, :] < ln[:, None]
        w = jax.random.exponential(kw, (chunk, hmax)) * live
        w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-30)
        return jnp.where(live, top, 0).astype(jnp.int32), w

    ids, w = jax.lax.map(one, (keys, labels, lens))
    return ids.reshape(-1, hmax), w.reshape(-1, hmax)


@functools.partial(jax.jit, static_argnames=("hmax", "chunk", "mesh"))
def _documents(key, logp, lengths, *, hmax, chunk, mesh=None):
    """(rows, hmax) ids and weights for ``lengths.shape[0]`` documents,
    ``chunk`` rows at a time; with a ``mesh``, over its chips."""
    return make_rows(functools.partial(_chunks, hmax=hmax), key, logp,
                     lengths, chunk, mesh)


def make(cfg: dict, seed: int, pool: int, mesh=None) -> Data:
    """The configuration's corpus and ``pool`` held-out queries; with a
    ``mesh``, the corpus rows over its ``model`` chips (the same rows)."""
    g = cfg["generator"]
    n, v, m, hmax = cfg["n"], cfg["v"], cfg["m"], cfg["hmax"]
    kv, kl, kql, kd, kq = jax.random.split(seed_key(seed), 5)
    coords, logp = _vocabulary(kv, v=v, m=m, classes=g["classes"],
                               topic_strength=g["topic_strength"],
                               zipf_s=g["zipf_s"])
    doc_len = shuffled(kl, lognormal_sizes(n, g["mean_words"],
                                           g["sigma_words"], 1, hmax))
    q_len = shuffled(kql, lognormal_sizes(pool, g["mean_words"],
                                          g["sigma_words"],
                                          g["min_query_words"], hmax))
    ids, w = _documents(kd, logp, jnp.asarray(doc_len), hmax=hmax,
                        chunk=g["chunk"], mesh=mesh)
    q_ids, q_w = _documents(kq, logp, jnp.asarray(q_len), hmax=hmax,
                            chunk=g["chunk"])
    return Data(ids=ids, w=w, coords=coords, q_ids=q_ids, q_w=q_w,
                doc_len=doc_len, q_len=q_len)
