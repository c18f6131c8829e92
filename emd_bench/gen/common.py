"""Seed handling and fixed size multisets shared by the generators.

Every seed draws the same multiset of sizes (document lengths, lit
pixels) in another order, so two seeds ask the device for
the same amount of work and differ only in which words, pixels and
queries they pick.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from scipy.special import ndtri


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (64 bits are kept, so seeds
    past 2**31 and negative seeds are distinct keys)."""
    seed = int(seed) & (2**64 - 1)
    data = jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def quantiles(count: int) -> np.ndarray:
    """Midpoint probabilities (i + 0.5) / count, i = 0 .. count-1."""
    return (np.arange(count) + 0.5) / count


def lognormal_sizes(count: int, mean: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    """``count`` whole sizes at the midpoint quantiles of a lognormal of
    the given mean and log-sd, clipped to [lo, hi]."""
    mu = math.log(mean) - sigma * sigma / 2
    raw = np.exp(mu + sigma * ndtri(quantiles(count)))
    return np.clip(np.rint(raw), lo, hi).astype(np.int32)


def normal_sizes(count: int, mean: float, sd: float, lo: int,
                 hi: int) -> np.ndarray:
    """``count`` whole sizes at the midpoint quantiles of a normal,
    clipped to [lo, hi]."""
    raw = mean + sd * ndtri(quantiles(count))
    return np.clip(np.rint(raw), lo, hi).astype(np.int32)


def shuffled(key: jax.Array, sizes: np.ndarray) -> np.ndarray:
    """``sizes`` in the order a seed's permutation gives."""
    perm = np.asarray(jax.random.permutation(key, sizes.shape[0]))
    return sizes[perm]


def plan(key, classes: int, lengths, chunk: int):
    """The key of the rows' chunks, and each row's class and size in
    (chunks, chunk) arrays: drawn whole, for every row."""
    rows = lengths.shape[0]
    kl, kc = jax.random.split(key)
    labels = jax.random.randint(kl, (rows,), 0, classes)
    pad = -rows % chunk
    labels = jnp.pad(labels, (0, pad)).reshape(-1, chunk)
    lens = jnp.pad(lengths, (0, pad)).reshape(-1, chunk)
    return kc, labels, lens


def make_rows(chunks, key, table, lengths, chunk: int, mesh=None):
    """(rows, hmax) ids and weights of ``lengths.shape[0]`` rows of
    ``table.shape[0]`` classes, ``chunk`` rows at a time:
    ``chunks(key, first, table, labels, lens)`` makes the chunks
    ``first``, ``first + 1``, ..., each from its index alone. With a
    ``mesh``, each chip of its ``model`` axis makes its own contiguous
    block of chunks and the rows come back as one array sharded over
    ``model``: the same rows, and no chip holds another's. (Traced inside
    the generator's jit.)"""
    kc, labels, lens = plan(key, table.shape[0], lengths, chunk)
    if mesh is None:
        ids, w = chunks(kc, 0, table, labels, lens)
        return ids[:lengths.shape[0]], w[:lengths.shape[0]]
    impl = jax.random.key_impl(kc)

    def local(key_data, table, labels, lens):
        first = jax.lax.axis_index("model") * labels.shape[0]
        return chunks(jax.random.wrap_key_data(key_data, impl=impl), first,
                      table, labels, lens)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(), P("model"), P("model")),
        out_specs=(P("model"), P("model")))(
            jax.random.key_data(kc), table, labels, lens)


@dataclasses.dataclass
class Data:
    """A generated deployment: the corpus and a pool of held-out queries,
    on the device, with each row's count of real (nonzero) bins on the
    host for the work counts."""
    ids: jax.Array          # (n, hmax) int32 vocabulary ids, 0 at padding;
                            # rows over the chips where the config says so
    w: jax.Array            # (n, hmax) float32 weights, 0 at padding
    coords: jax.Array       # (v, m) float32 vocabulary coordinates
    q_ids: jax.Array        # (pool, hmax) int32
    q_w: jax.Array          # (pool, hmax) float32
    doc_len: np.ndarray     # (n,) real bins per corpus row
    q_len: np.ndarray       # (pool,) real bins per query
