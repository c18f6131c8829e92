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


@dataclasses.dataclass
class Data:
    """A generated deployment: the corpus and a pool of held-out queries,
    on the device, with each row's count of real (nonzero) bins on the
    host for the work counts."""
    ids: jax.Array          # (n, hmax) int32 vocabulary ids, 0 at padding
    w: jax.Array            # (n, hmax) float32 weights, 0 at padding
    coords: jax.Array       # (v, m) float32 vocabulary coordinates
    q_ids: jax.Array        # (pool, hmax) int32
    q_w: jax.Array          # (pool, hmax) float32
    doc_len: np.ndarray     # (n,) real bins per corpus row
    q_len: np.ndarray       # (pool,) real bins per query
