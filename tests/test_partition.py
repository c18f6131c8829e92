"""Conformance of the ``kernels/partition`` shard_map shims — run in a
subprocess with 8 host devices (XLA_FLAGS must be set before jax
initializes, so these can't share the main single-device pytest
process).

The shims are what makes compiled ``pallas_call`` legal on a mesh
(a Pallas launch has no SPMD partitioning rule of its own); the
contract tested here is that routing a kernel launch through a shim is
INVISIBLE in the output: bitwise-identical scores to the single-host
kernel path, for every batched engine and every candidate kernel, plus
the divisibility fallback when the mesh axes don't divide the problem.
"""
import os
import subprocess
import sys

import pytest

_XLA_FLAGS = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count"))
_ENV = dict(os.environ,
            XLA_FLAGS=(_XLA_FLAGS
                       + " --xla_force_host_platform_device_count=8").strip(),
            PYTHONPATH="src", JAX_PLATFORMS="cpu")


def _run(script: str):
    res = subprocess.run([sys.executable, "-c", script], env=_ENV,
                         capture_output=True, text=True, cwd=".",
                         timeout=600)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


@pytest.mark.slow
def test_shim_paths_bitwise_match_single_host_kernels():
    """Every batched kernel engine (act/rwmd/omr) and every candidate
    kernel (act/rwmd/rwmd_rev/omr/ict) scores bitwise identically with
    and without the mesh shims on a (2, 4) mesh — the shims repartition
    the same launches, they never change the arithmetic."""
    out = _run("""
import jax, numpy as np
import jax.numpy as jnp
from repro.core import retrieval
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
corpus, _ = make_text_like(n_docs=64, n_classes=4, vocab=96, m=8,
                           doc_len=12, hmax=16, seed=7)
nq = 16
q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]

for method, iters in (("act", 3), ("rwmd", 0), ("omr", 0)):
    host = np.asarray(retrieval.batch_scores(
        corpus, q_ids, q_w, method=method, iters=iters, use_kernels=True))
    shim = np.asarray(retrieval.batch_scores(
        corpus, q_ids, q_w, method=method, iters=iters, use_kernels=True,
        mesh=mesh))
    np.testing.assert_array_equal(host, shim), method

rng = np.random.default_rng(0)
cand = jnp.asarray(rng.integers(0, corpus.n, size=(nq, 24)), jnp.int32)
for method, iters in (("act", 2), ("rwmd", 0), ("rwmd_rev", 0),
                      ("omr", 0), ("ict", 0)):
    host = np.asarray(retrieval.cand_scores(
        corpus, q_ids, q_w, cand, method=method, iters=iters,
        use_kernels=True))
    shim = np.asarray(retrieval.cand_scores(
        corpus, q_ids, q_w, cand, method=method, iters=iters,
        use_kernels=True, mesh=mesh))
    np.testing.assert_array_equal(host, shim), method
print("SHIM PARITY OK")
""")
    assert "SHIM PARITY OK" in out


@pytest.mark.slow
def test_shim_divisibility_fallback():
    """Shapes the mesh axes don't divide (odd query count; rows not a
    multiple of the model axis) fall back to the non-shim kernel path
    instead of crashing — still bitwise equal to the single-host
    launch. A vocabulary the model axis does not divide is padded and
    sharded."""
    out = _run("""
import jax, numpy as np
import jax.numpy as jnp
from repro.core import retrieval
from repro.data.synth import make_text_like
from repro.kernels import partition

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
corpus, _ = make_text_like(n_docs=63, n_classes=4, vocab=90, m=8,
                           doc_len=12, hmax=16, seed=7)
nq = 5                       # 5 % 2 != 0 -> queries not shardable
assert not partition.queries_shardable(mesh, nq)
assert not partition.rows_shardable(mesh, nq, corpus.n)
q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]
host = np.asarray(retrieval.batch_scores(
    corpus, q_ids, q_w, method="act", iters=2, use_kernels=True))
shim = np.asarray(retrieval.batch_scores(
    corpus, q_ids, q_w, method="act", iters=2, use_kernels=True,
    mesh=mesh))
np.testing.assert_array_equal(host, shim)

# divisible queries but indivisible rows: the pour falls back while
# Phase 1 (padded vocab) and the candidate shims still shard. Eight
# queries keep each data shard's stacked Phase-1 matmul at 64 columns:
# XLA:CPU rounds narrower matmuls differently, which would show up
# here as a 1-ulp mesh-vs-host difference unrelated to the fallback.
nq = 8
assert partition.queries_shardable(mesh, nq)
assert not partition.rows_shardable(mesh, nq, corpus.n)
q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]
rng = np.random.default_rng(1)
cand = jnp.asarray(rng.integers(0, corpus.n, size=(nq, 12)), jnp.int32)
host = np.asarray(retrieval.cand_scores(
    corpus, q_ids, q_w, cand, method="ict", iters=0, use_kernels=True))
shim = np.asarray(retrieval.cand_scores(
    corpus, q_ids, q_w, cand, method="ict", iters=0, use_kernels=True,
    mesh=mesh))
np.testing.assert_array_equal(host, shim)
print("FALLBACK OK")
""")
    assert "FALLBACK OK" in out
