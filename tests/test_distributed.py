"""Distributed integration tests — run in a subprocess with 8 host devices
(XLA_FLAGS must be set before jax initializes, so these can't share the
main pytest process, which runs single-device)."""
import os
import subprocess
import sys

import pytest

# Every subprocess gets exactly the 8-device host mesh these tests are
# written for (matching the CI job step's XLA_FLAGS): any inherited
# device-count flag is replaced, other exported XLA_FLAGS content (dump
# dirs etc.) is preserved, so local runs are self-sufficient regardless
# of the environment.
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
def test_sharded_train_step_runs_and_improves():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.launch import mesh as Mx, steps as St
from repro.models import model as M
from repro.models.config import InputShape
from repro.optim import adamw
from repro.data.tokens import DataConfig, global_batch

mesh = Mx.make_test_mesh(2, 2, multi_pod=True)
cfg = smoke_config("olmo-1b")
shape = InputShape("t", 32, 8, "train")
step, _ = St.jit_train_step(cfg, shape, mesh,
                            opt_cfg=adamw.AdamWConfig(peak_lr=3e-3,
                                                      warmup_steps=2,
                                                      total_steps=40))
params = M.init(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params, cfg.opt_state_dtype)
dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=0)
losses = []
with jax.set_mesh(mesh):
    for s in range(30):
        b = {k: jnp.asarray(v) for k, v in global_batch(dc, s).items()}
        params, opt, m = step(params, opt, b)
        losses.append(float(m["loss"]))
assert all(np.isfinite(losses)), losses
assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses
print("TRAIN OK", losses[0], "->", losses[-1])
""")
    assert "TRAIN OK" in out


@pytest.mark.slow
def test_elastic_reshard_8_to_4():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.checkpoint import store
from repro.runtime import elastic
from repro.models import model as M
import tempfile

cfg = smoke_config("olmo-1b")
params = M.init(jax.random.PRNGKey(0), cfg)
d = tempfile.mkdtemp()
store.save(d, 5, params)

from repro.launch.mesh import make_mesh
mesh8 = make_mesh((4, 2), ("data", "model"))
mesh4 = make_mesh((2, 2), ("data", "model"))
p8 = elastic.restore_on_mesh(d, 5, params, mesh8)
p4 = elastic.restore_on_mesh(d, 5, params, mesh4)
for a, b, c in zip(jax.tree.leaves(params), jax.tree.leaves(p8),
                   jax.tree.leaves(p4), strict=True):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
# live reshard between meshes
p4b = elastic.reshard_live(p8, mesh4)
for a, b in zip(jax.tree.leaves(p8), jax.tree.leaves(p4b), strict=True):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC OK")
""")
    assert "ELASTIC OK" in out


@pytest.mark.slow
def test_compressed_cross_pod_psum():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.optim.grad_utils import compressed_psum_tree

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("pod",))
shard_map = partial(jax.shard_map, check_vma=False)

@partial(shard_map, mesh=mesh, in_specs=(P("pod"), P()),
         out_specs=P("pod"))
def reduce_grads(g, key):
    return compressed_psum_tree({"g": g}, key, "pod")["g"]

g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
key = jax.random.PRNGKey(1)
with jax.set_mesh(mesh):
    out = reduce_grads(g, key)
exact = jnp.broadcast_to(jnp.sum(g, 0, keepdims=True), g.shape)
rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
assert rel < 0.05, rel
print("COMPRESSED PSUM OK", rel)
""")
    assert "COMPRESSED PSUM OK" in out


@pytest.mark.slow
def test_distributed_search_matches_reference():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.data.synth import make_text_like
from repro.launch.search import make_search_step, search_shardings, jit_search_step
from repro.core import lc
from repro.configs.emd_20news import EMDWorkload

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=16, vocab=64, m=8, doc_len=24, hmax=16)
w = EMDWorkload(name="t", n_db=16, vocab=64, dim=8, hmax=16, iters=2,
                queries=8)
step = jit_search_step(w, mesh, top_l=4)
q_ids, q_w = corpus.ids[:8], corpus.w[:8]
with jax.set_mesh(mesh):
    scores, idx = step(corpus.ids, corpus.w, corpus.coords, q_ids, q_w)
# reference: single-device engine
for u in range(8):
    ref = lc.lc_act_scores(corpus, q_ids[u], q_w[u], iters=2)
    neg, ridx = jax.lax.top_k(-ref, 4)
    np.testing.assert_allclose(np.asarray(scores[u]), np.asarray(-neg),
                               rtol=1e-5, atol=1e-6)
print("SEARCH OK")
""")
    assert "SEARCH OK" in out


@pytest.mark.slow
def test_distributed_every_method_matches_batched():
    """Acceptance: EVERY method in retrieval.METHODS scores identically
    (within tolerance) on backend="distributed" over an 8-device (4, 2)
    mesh vs the single-host batched engine — including pad rows
    (pad_multiple pads 24 -> 32) and a block_q that divides neither the
    query count nor the per-shard count. Also covers the symmetric
    measure on the mesh."""
    out = _run("""
import dataclasses, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.core.retrieval import METHODS
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=24, vocab=64, m=8, doc_len=10, hmax=16)
q_ids, q_w = corpus.ids[:5], corpus.w[:5]       # odd nq: padded to the mesh
assert bool((np.asarray(q_w) == 0.0).any())     # query-side padding in play
for method in sorted(METHODS):
    cfg = EngineConfig(method=method, iters=2, backend="distributed",
                       pad_multiple=16, block_q=3)
    dst = EmdIndex.build(corpus, cfg, mesh=mesh)
    assert dst._padded_corpus.n == 32 > corpus.n
    ref = EmdIndex.build(corpus, dataclasses.replace(cfg,
                                                     backend="reference"))
    np.testing.assert_allclose(np.asarray(dst.scores(q_ids, q_w)),
                               np.asarray(ref.scores(q_ids, q_w)),
                               rtol=1e-5, atol=1e-6, err_msg=method)
    _, idx = dst.search(q_ids, q_w, top_l=8)
    assert int(np.asarray(idx).max()) < corpus.n, method   # pads masked
    print("METHOD OK", method)
sym = EngineConfig(method="rwmd", symmetric=True, backend="distributed",
                   pad_multiple=16, block_q=3)
d = EmdIndex.build(corpus, sym, mesh=mesh)
r = EmdIndex.build(corpus, dataclasses.replace(sym, backend="reference"))
np.testing.assert_allclose(np.asarray(d.scores(q_ids, q_w)),
                           np.asarray(r.scores(q_ids, q_w)),
                           rtol=1e-5, atol=1e-6)
print("ALL METHODS OK")
""")
    assert "ALL METHODS OK" in out
    for method in ("act", "bow", "omr", "rwmd", "rwmd_rev", "wcd"):
        assert f"METHOD OK {method}" in out


@pytest.mark.slow
def test_distributed_all_pairs_dedup_matches_reference():
    """Corpus-as-queries all-pairs on a small vocabulary crosses the
    unique-bin dedup gate INSIDE the SPMD step (jnp.unique + inverse
    gather over DP-sharded query ids) — parity vs the single-host
    engine, which crosses the same gate."""
    out = _run("""
import dataclasses, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.core import lc
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=24, n_classes=4, vocab=40, m=6,
                           doc_len=30, hmax=16)
assert corpus.n * corpus.hmax >= lc.DEDUP_STACK_RATIO * corpus.v
cfg = EngineConfig(method="rwmd", iters=0, backend="distributed",
                   pad_multiple=8, block_q=5)
dst = EmdIndex.build(corpus, cfg, mesh=mesh)
ref = EmdIndex.build(corpus, dataclasses.replace(cfg, backend="reference"))
np.testing.assert_allclose(np.asarray(dst.all_pairs()),
                           np.asarray(ref.all_pairs()),
                           rtol=1e-5, atol=1e-6)
print("DEDUP SPMD OK")
""")
    assert "DEDUP SPMD OK" in out


@pytest.mark.slow
def test_distributed_scan_engine_matches_batched_step():
    """batch_engine="scan" on the mesh replays the per-query graphs — the
    verification escape hatch exists on the distributed backend too."""
    out = _run("""
import dataclasses, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
corpus, _ = make_text_like(n_docs=16, vocab=64, m=8, doc_len=24, hmax=16)
cfg = EngineConfig(method="act", iters=2, backend="distributed",
                   pad_multiple=8)
fast = EmdIndex.build(corpus, cfg, mesh=mesh)
slow = EmdIndex.build(corpus, dataclasses.replace(cfg, batch_engine="scan"),
                      mesh=mesh)
q_ids, q_w = corpus.ids[:4], corpus.w[:4]
np.testing.assert_allclose(np.asarray(fast.scores(q_ids, q_w)),
                           np.asarray(slow.scores(q_ids, q_w)),
                           rtol=1e-5, atol=1e-6)
print("SCAN STEP OK")
""")
    assert "SCAN STEP OK" in out


@pytest.mark.slow
def test_distributed_cascade_exact_and_matches_reference():
    """Cascaded prune-and-rescore on the 8-device (4, 2) mesh: the
    shard-blocked stage-wise top-budget (topk_blocks = model axis size,
    ladder-merged winners) produces (a) the identical top-l as the
    single-host cascade for the same spec, and (b) the admissible-cascade
    exactness property — budgets covering every true top-l neighbor's
    stage rank => identical top-l index set as full-corpus rescoring —
    for both the act and ict rescorers. Pad rows (24 -> 32) in play."""
    out = _run("""
import dataclasses, jax, numpy as np
import jax.numpy as jnp
from repro import cascade
from repro.api import EmdIndex, EngineConfig
from repro.cascade import CascadeSpec, CascadeStage, rescore
from repro.core import retrieval
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=24, n_classes=4, vocab=64, m=8,
                           doc_len=10, hmax=16, seed=5)
nq, top_l = 5, 3
q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]

for rescorer, stages in (("act", (("rwmd", 0), ("omr", 0))),
                         ("ict", (("rwmd", 0), ("act", 1)))):
    iters = 2 if rescorer == "act" else 1
    all_rows = jnp.broadcast_to(jnp.arange(corpus.n, dtype=jnp.int32),
                                (nq, corpus.n))
    full = np.asarray(rescore.resolve(rescorer).fn(
        corpus, q_ids, q_w, all_rows, iters=iters))
    ref_idx = np.argsort(full, axis=1, kind="stable")[:, :top_l]
    budgets = []
    for m, it in stages:
        s = np.asarray(retrieval.batch_scores(corpus, q_ids, q_w,
                                              method=m, iters=it))
        order = np.argsort(s, axis=1, kind="stable")
        rank = np.empty_like(order)
        np.put_along_axis(rank, order,
                          np.arange(s.shape[1])[None, :], axis=1)
        budgets.append(max(top_l,
                           int(np.take_along_axis(rank, ref_idx,
                                                  axis=1).max()) + 1))
    for i in range(len(budgets) - 2, -1, -1):
        budgets[i] = max(budgets[i], budgets[i + 1])
    spec = CascadeSpec(stages=tuple(
        CascadeStage(m, b, iters=it)
        for (m, it), b in zip(stages, budgets, strict=True)),
        rescorer=rescorer, rescorer_iters=iters)
    assert spec.admissible

    cfg = EngineConfig(method="act", iters=iters, top_l=top_l,
                       cascade=spec, backend="distributed",
                       pad_multiple=16, block_q=3)
    dst = EmdIndex.build(corpus, cfg, mesh=mesh)
    assert dst._padded_corpus.n == 32 > corpus.n
    s_d, i_d = dst.search(q_ids, q_w)
    # (a) parity with the single-host cascade
    ref = EmdIndex.build(corpus,
                         dataclasses.replace(cfg, backend="reference"))
    s_r, i_r = ref.search(q_ids, q_w)
    np.testing.assert_array_equal(np.sort(np.asarray(i_d), 1),
                                  np.sort(np.asarray(i_r), 1))
    np.testing.assert_allclose(np.sort(np.asarray(s_d), 1),
                               np.sort(np.asarray(s_r), 1),
                               rtol=1e-5, atol=1e-6)
    # (b) admissible-cascade exactness vs full-corpus rescoring
    np.testing.assert_array_equal(np.sort(np.asarray(i_d), 1),
                                  np.sort(ref_idx, 1))
    assert int(np.asarray(i_d).max()) < corpus.n      # pads masked
    print("CASCADE MESH OK", rescorer, budgets)
print("ALL CASCADE OK")
""")
    assert "ALL CASCADE OK" in out
    assert "CASCADE MESH OK act" in out
    assert "CASCADE MESH OK ict" in out


@pytest.mark.slow
def test_distributed_cascade_kernel_conformance():
    """The fused candidate kernels inside the mesh cascade step on the
    8-device (4, 2) mesh: ``use_kernels=True`` (interpret-mode Pallas
    lowers to plain HLO, so SPMD shards it like any other op) returns
    the identical top-l set as (a) the non-kernel mesh cascade and
    (b) full-corpus rescoring — the acceptance criterion's mesh half.
    Budgets cover the true neighbors' stage ranks under both paths."""
    out = _run("""
import jax, numpy as np
import jax.numpy as jnp
from repro.cascade import CascadeSpec, CascadeStage, rescore
from repro.configs.emd_20news import EMDWorkload
from repro.core import retrieval
from repro.core.lc import Corpus
from repro.data.synth import make_text_like
from repro.launch import search as Sx

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=24, n_classes=4, vocab=64, m=8,
                           doc_len=10, hmax=16, seed=5)
nq, top_l, iters = 5, 3, 2
q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]
stages = (("rwmd", 0), ("omr", 0))

# budgets covering the true act top-l ranks under BOTH paths
budget_req = []
for uk in (False, True):
    all_rows = jnp.broadcast_to(jnp.arange(corpus.n, dtype=jnp.int32),
                                (nq, corpus.n))
    full = np.asarray(rescore.resolve("act").fn(
        corpus, q_ids, q_w, all_rows, iters=iters, use_kernels=uk))
    ref_idx = np.argsort(full, axis=1, kind="stable")[:, :top_l]
    req = []
    for m, it in stages:
        s = np.asarray(retrieval.batch_scores(corpus, q_ids, q_w,
                                              method=m, iters=it,
                                              use_kernels=uk))
        order = np.argsort(s, axis=1, kind="stable")
        rank = np.empty_like(order)
        np.put_along_axis(rank, order,
                          np.arange(s.shape[1])[None, :], axis=1)
        req.append(max(top_l,
                       int(np.take_along_axis(rank, ref_idx,
                                              axis=1).max()) + 1))
    budget_req.append(req)
budgets = [max(a, b) for a, b in zip(*budget_req, strict=True)]
for i in range(len(budgets) - 2, -1, -1):
    budgets[i] = max(budgets[i], budgets[i + 1])
spec = CascadeSpec(stages=tuple(CascadeStage(m, b, iters=it)
                                for (m, it), b in zip(stages, budgets, strict=True)),
                   rescorer="act", rescorer_iters=iters)
assert spec.admissible

workload = EMDWorkload(name="t", n_db=corpus.n, vocab=corpus.v,
                       dim=corpus.m, hmax=corpus.hmax, iters=iters,
                       queries=nq, method="act")
n_pad = 32
padded = Corpus(ids=jnp.pad(corpus.ids, ((0, n_pad - corpus.n), (0, 0))),
                w=jnp.pad(corpus.w, ((0, n_pad - corpus.n), (0, 0))),
                coords=corpus.coords)
in_sh, _ = Sx.search_shardings(mesh, workload)
p_ids = jax.device_put(padded.ids, in_sh[0])
p_w = jax.device_put(padded.w, in_sh[1])
coords = jax.device_put(padded.coords, in_sh[2])
qi = jnp.pad(q_ids, ((0, 8 - nq), (0, 0)))      # data axis = 4: pad to 8
qw = jnp.pad(q_w, ((0, 8 - nq), (0, 0)))

results = {}
with jax.set_mesh(mesh):
    for uk in (False, True):
        step = Sx.jit_cascade_search_step(workload, mesh, spec,
                                          top_l=top_l, pad_multiple=16,
                                          block_q=3, use_kernels=uk)
        s, i = step(p_ids, p_w, coords, qi, qw)
        results[uk] = (np.asarray(s)[:nq], np.asarray(i)[:nq])

i_ref, i_ker = results[False][1], results[True][1]
np.testing.assert_array_equal(np.sort(i_ker, 1), np.sort(i_ref, 1))
np.testing.assert_allclose(np.sort(results[True][0], 1),
                           np.sort(results[False][0], 1),
                           rtol=1e-6, atol=1e-7)
assert int(i_ker.max()) < corpus.n                # pads masked
full = np.asarray(rescore.resolve("act").fn(
    corpus, q_ids, q_w,
    jnp.broadcast_to(jnp.arange(corpus.n, dtype=jnp.int32),
                     (nq, corpus.n)), iters=iters))
ref_idx = np.argsort(full, axis=1, kind="stable")[:, :top_l]
np.testing.assert_array_equal(np.sort(i_ker, 1), np.sort(ref_idx, 1))
print("CASCADE KERNEL MESH OK", budgets)
""")
    assert "CASCADE KERNEL MESH OK" in out


@pytest.mark.slow
def test_distributed_compiled_cascade_matches_interpret_oracle():
    """The acceptance parity check: the distributed kernel cascade —
    every Pallas launch routed through the ``kernels/partition``
    shard_map shims, the structure that compiles on real device meshes —
    returns the exact top-l of the single-host ``backend="pallas"``
    cascade (the interpret-mode conformance oracle) on the 8-device
    (2, 4) mesh, end to end through ``EmdIndex``."""
    out = _run("""
import jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
corpus, _ = make_text_like(n_docs=64, n_classes=4, vocab=96, m=8,
                           doc_len=12, hmax=16, seed=7)
nq, top_l = 16, 4
q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]
cfg = EngineConfig(method="act", iters=2, top_l=top_l, cascade="fast",
                   backend="pallas")
assert cfg.score_kwargs()["use_kernels"]
oracle = EmdIndex.build(corpus, cfg)
s_o, i_o = oracle.search(q_ids, q_w)

import dataclasses
dcfg = dataclasses.replace(cfg, backend="distributed", pad_multiple=8)
assert dcfg.score_kwargs()["use_kernels"]   # kernels stay ON on the mesh
dist = EmdIndex.build(corpus, dcfg, mesh=mesh)
s_d, i_d = dist.search(q_ids, q_w)
np.testing.assert_array_equal(np.sort(np.asarray(i_d), 1),
                              np.sort(np.asarray(i_o), 1))
np.testing.assert_allclose(np.sort(np.asarray(s_d), 1),
                           np.sort(np.asarray(s_o), 1),
                           rtol=1e-6, atol=1e-7)
print("COMPILED CASCADE PARITY OK")
""")
    assert "COMPILED CASCADE PARITY OK" in out


@pytest.mark.slow
def test_emd_index_distributed_backend_multi_device():
    """EmdIndex(backend='distributed') on an 8-device (4, 2) mesh matches
    the reference backend — identical code path as single-host callers."""
    out = _run("""
import jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=24, vocab=64, m=8, doc_len=24, hmax=16)
ref = EmdIndex.build(corpus, EngineConfig(method="act", iters=2, top_l=4))
dst = EmdIndex.build(corpus, EngineConfig(method="act", iters=2, top_l=4,
                                          backend="distributed",
                                          pad_multiple=8), mesh=mesh)
# odd batch size: not divisible by the data axis -> padded internally
q_ids, q_w = corpus.ids[:5], corpus.w[:5]
s_ref = np.asarray(ref.scores(q_ids, q_w))
s_dst = np.asarray(dst.scores(q_ids, q_w))
np.testing.assert_allclose(s_ref, s_dst, rtol=1e-5, atol=1e-6)
t_ref, i_ref = ref.search(q_ids, q_w)
t_dst, i_dst = dst.search(q_ids, q_w)
np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_dst))
print("INDEX DIST OK")
""")
    assert "INDEX DIST OK" in out


@pytest.mark.slow
def test_static_check_cli_clean_on_main():
    """The full static-check CLI (registry + hazards + vmem +
    collectives vs the committed golden manifest) exits 0 on the repo as
    it stands — the same invocation CI's static-checks job runs."""
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis.check"], env=_ENV,
        capture_output=True, text=True, cwd=".", timeout=600)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for passname in ("registry", "hazards", "vmem", "collectives"):
        assert f"PASS {passname}" in res.stdout, res.stdout


@pytest.mark.slow
def test_collective_scaling_guard_catches_seeded_gather():
    """Seed the violation the scaling guard exists for: a step whose
    (nq, n) score matrix is forced replicated (one all-gather of the
    whole matrix over 'model'), compiled at the guard's two corpus
    sizes. The guard must flag it, and must stay quiet on the real
    registry-built steps at the same sizes."""
    out = _run("""
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis import collectives_check as C
from repro.launch import search as S

mesh = C.make_mesh()
cases = {c.name: c for c in S.step_cases()}
case = cases["scores:rwmd:dist"]

def bad_step_fn(workload):
    step = S.make_scores_step(workload.iters, method="rwmd", engine="dist")
    def bad(ids, w, coords, q_ids, q_w):
        s = step(ids, w, coords, q_ids, q_w)
        # Replicate the (nq, n) score matrix: the corpus-scaled
        # all-gather the shard-local contract forbids.
        return jax.lax.with_sharding_constraint(
            s, NamedSharding(mesh, P(None, None)))
    in_sh, _ = S.search_shardings(mesh, workload)
    return jax.jit(bad, in_shardings=in_sh,
                   out_shardings=NamedSharding(mesh, P(None, None)))

n0, n1 = C.SCALE_N_DBS
violations = C.check_scaling(
    case, mesh,
    small_fn=bad_step_fn(C.check_workload(n0)),
    big_fn=bad_step_fn(C.check_workload(n1)))
assert violations, "seeded corpus-scaled all-gather not flagged"
assert "scale with the corpus" in violations[0].message, violations
assert C.check_scaling(case, mesh) == []          # real step stays clean
assert C.check_scaling(cases["cascade:pinned:dist"], mesh) == []
print("SCALING GUARD OK", violations[0].message[:60])
""")
    assert "SCALING GUARD OK" in out


@pytest.mark.slow
def test_reshard_live_index_tables_8_to_4_to_8():
    """Satellite of the serving PR: survivor-only recovery of a BUILT
    index. ``elastic.reshard_live`` moves the Phase-1 tables of an
    8-device index onto the surviving 4-device mesh in memory (no
    checkpoint round-trip), parity-checked against a full rebuild, and
    the resharded tables actually serve — spliced under the small mesh's
    jitted step they return the identical top-l. Then back up 4 -> 8
    (the node returns)."""
    out = _run("""
import dataclasses, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.configs.emd_20news import EMDWorkload
from repro.core.lc import Corpus
from repro.data.synth import make_text_like
from repro.launch import search as dsearch
from repro.runtime import elastic

corpus, _ = make_text_like(n_docs=24, vocab=64, m=8, doc_len=10, hmax=16)
cfg = EngineConfig(method="act", iters=2, top_l=4, backend="distributed",
                   pad_multiple=8)
from repro.launch.mesh import make_mesh
mesh8 = make_mesh((4, 2), ("data", "model"))
mesh4 = make_mesh((2, 2), ("data", "model"))
idx8 = EmdIndex.build(corpus, cfg, mesh=mesh8)
q_ids, q_w = corpus.ids[:5], corpus.w[:5]
s8, i8 = idx8.search(q_ids, q_w)

def table_shardings(mesh):
    w = EMDWorkload(name="emd-index", n_db=corpus.n, vocab=corpus.v,
                    dim=corpus.m, hmax=corpus.hmax,
                    iters=cfg.effective_iters, queries=0, method=cfg.method)
    in_sh, _ = dsearch.scores_shardings(mesh, w, method=cfg.method)
    return {"ids": in_sh[0], "w": in_sh[1], "coords": in_sh[2]}

tables8 = {"ids": idx8._padded_corpus.ids, "w": idx8._padded_corpus.w,
           "coords": idx8._padded_corpus.coords}
t4 = elastic.reshard_live(tables8, mesh4, shardings=table_shardings(mesh4))
dev4 = set(mesh4.devices.ravel().tolist())
for leaf in jax.tree.leaves(t4):
    assert set(leaf.devices()) <= dev4, (leaf.devices(), dev4)
# parity vs a full rebuild on the surviving mesh
idx4 = EmdIndex.build(corpus, cfg, mesh=mesh4)
for k in tables8:
    np.testing.assert_array_equal(np.asarray(t4[k]),
                                  np.asarray(getattr(idx4._padded_corpus, k)))
# the resharded tables SERVE under the small mesh's step
idx4b = dataclasses.replace(idx4, _padded_corpus=Corpus(**t4))
s4, i4 = idx4b.search(q_ids, q_w)
np.testing.assert_array_equal(np.asarray(i8), np.asarray(i4))
np.testing.assert_allclose(np.asarray(s8), np.asarray(s4),
                           rtol=1e-5, atol=1e-6)
# scale back up: 4 -> 8
t8 = elastic.reshard_live(t4, mesh8, shardings=table_shardings(mesh8))
for k in tables8:
    np.testing.assert_array_equal(np.asarray(t8[k]), np.asarray(tables8[k]))
idx8b = dataclasses.replace(idx8, _padded_corpus=Corpus(**t8))
s8b, i8b = idx8b.search(q_ids, q_w)
np.testing.assert_array_equal(np.asarray(i8), np.asarray(i8b))
print("RESHARD LIVE OK")
""")
    assert "RESHARD LIVE OK" in out


@pytest.mark.slow
def test_distributed_sourced_cascade_matches_single_host():
    """Sourced cascades (both sublinear sources) on the 8-device (4, 2)
    mesh: the source state rides into the SPMD step as replicated
    trailing operands, and the distributed top-l matches the single-host
    reference backend fed the SAME built source — for the IVF/LSH source
    with the exact-centroid refine path on and for the cluster tree."""
    out = _run("""
import dataclasses, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.candidates import CentroidLSHSpec, ClusterTreeSpec
from repro.cascade import CascadeSpec, CascadeStage
from repro.data.synth import make_clustered_text

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_clustered_text(90, n_topics=4, vocab=128, m=8, hmax=16,
                                min_len=8, seed=3)
q_ids, q_w = corpus.ids[:5], corpus.w[:5]       # odd nq: padded to the mesh
for src_spec in (CentroidLSHSpec(n_buckets=8, probes=4, bucket_cap=24,
                                 refine=48),
                 ClusterTreeSpec(branching=4, depth=2, beam=4, probes=3,
                                 leaf_cap=16)):
    spec = CascadeSpec(stages=(CascadeStage("rwmd", 16),),
                       rescorer="act", rescorer_iters=2, source=src_spec)
    cfg = EngineConfig(method="act", iters=2, top_l=4, cascade=spec,
                       backend="distributed", pad_multiple=16, block_q=3)
    dst = EmdIndex.build(corpus, cfg, mesh=mesh)
    assert dst._padded_corpus.n > corpus.n          # pad rows in play
    ref = EmdIndex.build(corpus,
                         dataclasses.replace(cfg, backend="reference"),
                         source=dst.source)         # same built source
    s_d, i_d = dst.search(q_ids, q_w)
    s_r, i_r = ref.search(q_ids, q_w)
    np.testing.assert_array_equal(np.sort(np.asarray(i_d), 1),
                                  np.sort(np.asarray(i_r), 1))
    np.testing.assert_allclose(np.sort(np.asarray(s_d), 1),
                               np.sort(np.asarray(s_r), 1),
                               rtol=1e-5, atol=1e-6)
    assert int(np.asarray(i_d).max()) < corpus.n    # pads masked
    print("SOURCED MESH OK", src_spec.kind)
print("ALL SOURCED OK")
""")
    assert "ALL SOURCED OK" in out
    assert "SOURCED MESH OK centroid_lsh" in out
    assert "SOURCED MESH OK cluster_tree" in out


@pytest.mark.slow
def test_sourced_cascade_traffic_stays_flat():
    """The subsystem's core promise under the scaling guard: compiling
    the sourced cascade steps at the guard's two corpus sizes, cross-mesh
    traffic and FLOPs must NOT grow with the corpus (only the replicated
    source state and probed gathers may appear) — for the LSH source
    (refine on), its kernel variant, and the cluster tree."""
    out = _run("""
from repro.analysis import collectives_check as C
from repro.launch import search as S

mesh = C.make_mesh()
cases = {c.name: c for c in S.step_cases()}
for name in ("cascade:sourced:lsh:dist", "cascade:sourced:lsh:dist:kernels",
             "cascade:sourced:tree:dist"):
    case = cases[name]
    assert case.scale_guarded
    assert C.check_scaling(case, mesh) == [], name
    print("FLAT OK", name)
print("ALL FLAT OK")
""")
    assert "ALL FLAT OK" in out
    assert "FLAT OK cascade:sourced:tree:dist" in out


@pytest.mark.slow
def test_emd_server_recovers_on_mesh_change():
    """Serving-level recovery on mesh change: a live EmdServer over a
    distributed-backend index rebuilds every tier on the surviving mesh
    as a new generation (in-flight semantics preserved) and keeps
    serving identical results."""
    out = _run("""
import asyncio, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.data.synth import make_text_like
from repro.serving import EmdServer, ServingPolicy

corpus, _ = make_text_like(n_docs=24, vocab=64, m=8, doc_len=10, hmax=16)
cfg = EngineConfig(method="act", iters=2, top_l=4, backend="distributed",
                   pad_multiple=8)
from repro.launch.mesh import make_mesh
mesh8 = make_mesh((4, 2), ("data", "model"))
mesh4 = make_mesh((2, 2), ("data", "model"))
index = EmdIndex.build(corpus, cfg, mesh=mesh8)
policy = ServingPolicy(ladder=("primary", "wcd"), max_batch=4,
                       flush_ms=5.0, backoff_ms=0.0, deadline_ms=60_000)

async def main():
    async with EmdServer(index, policy) as server:
        before = await server.search(corpus.ids[0], corpus.w[0])
        server.reshard(mesh4)            # half the machine went away
        after = await server.search(corpus.ids[0], corpus.w[0])
        assert after.generation == before.generation + 1
        np.testing.assert_array_equal(before.scores, after.scores)
        np.testing.assert_array_equal(before.indices, after.indices)
        server.reshard(mesh8)            # and came back
        again = await server.search(corpus.ids[0], corpus.w[0])
        np.testing.assert_array_equal(before.scores, again.scores)

asyncio.run(main())
print("SERVER MESH RECOVERY OK")
""")
    assert "SERVER MESH RECOVERY OK" in out
