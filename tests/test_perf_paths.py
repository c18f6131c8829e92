"""Equivalence tests for the performance-path variants vs the plain paths:
flash attention, partitionable top-k, packed/shard_map MoE, remat policies.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.configs import smoke_config
from repro.core.lc import smallest_k
from repro.models import model as M


def test_flash_attention_matches_dense(rng):
    B, S, KV, G, hd = 2, 2048, 2, 3, 32
    q = jnp.asarray(rng.normal(size=(B, S, KV, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    for window in (0, 100, 513):
        out_f = L._flash_attention(q, k, v, jnp.int32(window), hd ** -0.5)
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        ok = kpos <= qpos
        ok &= jnp.where(window > 0, (qpos - kpos) < window, True)
        mask = jnp.where(ok, 0.0, L.NEG_INF)
        s = (jnp.einsum("bqngh,btnh->bqngt", q * hd ** -0.5, k)
             + mask[None, :, None, None, :])
        out_d = jnp.einsum("bqngt,btnh->bqngh", jax.nn.softmax(s, -1), v)
        err = float(jnp.max(jnp.abs(out_f - out_d)))
        assert err < 1e-4, (window, err)


@pytest.mark.parametrize("shape", [(40, 17), (3, 64, 9)])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_smallest_k_matches_lax_top_k(shape, k, rng):
    d = jnp.asarray(rng.normal(size=shape), jnp.float32)
    z, s = smallest_k(d, k)
    neg, sr = jax.lax.top_k(-d, k)
    np.testing.assert_allclose(np.asarray(z), np.asarray(-neg), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def _moe_batch(cfg, rng):
    B, S = 2, 16
    return {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
            "labels": jnp.zeros((B, S), jnp.int32)}


def test_packed_moe_equivalent():
    cfg1 = smoke_config("mixtral-8x22b")
    rng = jax.random.PRNGKey(0)
    params = M.init(rng, cfg1)
    batch = _moe_batch(cfg1, rng)
    y1, _, _ = M.forward(params, batch, cfg1)
    cfg2 = dataclasses.replace(cfg1, moe_ff_shards=2)
    blocks = dict(params["blocks"])
    moe = dict(blocks["moe"])
    moe["w_up"] = jax.vmap(lambda w: L.pack_moe_weights(w, 2))(moe["w_up"])
    moe["w_gate"] = jax.vmap(lambda w: L.pack_moe_weights(w, 2))(moe["w_gate"])
    moe["w_down"] = jax.vmap(lambda w: L.pack_moe_down(w, 2))(moe["w_down"])
    blocks["moe"] = moe
    p2 = dict(params)
    p2["blocks"] = blocks
    y2, _, _ = M.forward(p2, batch, cfg2)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-3


def test_remat_policy_dots_same_loss_and_grads():
    cfg = dataclasses.replace(smoke_config("olmo-1b"), remat=True)
    cfg_d = dataclasses.replace(cfg, remat_policy="dots")
    rng = jax.random.PRNGKey(1)
    params = M.init(rng, cfg)
    batch = {"tokens": jax.random.randint(rng, (2, 16), 0, cfg.vocab),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    l1, g1 = jax.value_and_grad(lambda p: M.train_loss(p, batch, cfg))(params)
    l2, g2 = jax.value_and_grad(lambda p: M.train_loss(p, batch, cfg_d))(params)
    assert abs(float(l1) - float(l2)) < 1e-6
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2), strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


@pytest.mark.slow
def test_moe_shard_map_matches_constraint_path():
    """shard_map EP == plain path, on a real 8-device mesh (subprocess)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    script = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.models import model as M
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = smoke_config("mixtral-8x22b")          # E=4 experts over model=4
cfg_sm = dataclasses.replace(cfg, moe_shard_map=True)
params = M.init(jax.random.PRNGKey(0), cfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                      cfg.vocab)}
y_ref, _, _ = M.forward(params, batch, cfg)
with jax.set_mesh(mesh):
    y_sm = jax.jit(lambda p, b: M.forward(p, b, cfg_sm)[0])(params, batch)
err = float(jnp.max(jnp.abs(y_ref - y_sm)))
assert err < 1e-3, err
print("SHMAP OK", err)
"""
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHMAP OK" in res.stdout
