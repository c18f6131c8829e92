"""Precision-policy suite: sentinel representability, reduced-precision
selection and masking regressions, policy threading end to end, VMEM
layout halving, snapshot round-trips, and the static precision lint.

The sentinel bugfixes under test (see ``repro.core.precision``):

* ``lc.PAD_DIST`` (1e30) OVERFLOWS float16 to inf and ROUNDS in
  bfloat16, so every reduced-precision path writes
  ``pad_dist_for(dtype)`` — finite, exactly representable, and (where
  the dtype's range allows) at least the float32 sentinel on upcast;
* ``retrieval._mask_self`` masks in the float32 ACCUMULATOR dtype:
  ``finfo(bfloat16).max`` is also bf16's overflow-saturation value, so
  an in-dtype mask would tie the diagonal with saturated entries and
  let top_k's index order retrieve self;
* checkpoint restore preserves leaf dtypes — a stored-vs-target
  mismatch is a typed error, never a silent cast.
"""
import asyncio
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EmdIndex, EngineConfig
from repro.checkpoint import store
from repro.checkpoint.store import CheckpointCorrupt
from repro.core import lc, retrieval
from repro.core.lc import PAD_DIST
from repro.core.precision import (POLICIES, PrecisionPolicy, pad_dist_for,
                                  resolve)
from repro.data.synth import make_text_like
from repro.kernels import ops as kops

_PAD_F32 = float(np.float32(1e30))


@pytest.fixture(scope="module")
def corpus():
    return make_text_like(n_docs=24, vocab=48, m=8, doc_len=12, hmax=12,
                          seed=5)[0]


# ----------------------------------------------------- sentinel contract

def test_pad_dist_f32_is_bitwise_historical():
    assert pad_dist_for(jnp.float32) == _PAD_F32
    assert np.float32(pad_dist_for("float32")) == np.float32(PAD_DIST)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_pad_dist_properties(dtype):
    """Finite, below the dtype max, exactly representable (downcast/
    upcast round-trips bit-exact), and above any real transport cost."""
    pad = pad_dist_for(dtype)
    fi = jnp.finfo(jnp.dtype(dtype))
    assert np.isfinite(pad)
    assert pad < float(fi.max)
    roundtrip = float(jnp.asarray(pad, jnp.dtype(dtype)))
    assert roundtrip == pad, f"{dtype} sentinel not exactly representable"
    assert pad > 1e3        # any unit-scale transport cost stays below


def test_pad_dist_upcast_clears_f32_sentinel_where_range_allows():
    """Strict ``< pad`` comparisons stay correct across a mixed handoff:
    a bf16-stored sentinel upcast to float32 must not drop below the
    float32 sentinel (float16 cannot reach 1e30 — its sentinel only
    needs to exceed real costs, which the property test covers)."""
    assert float(jnp.asarray(pad_dist_for(jnp.bfloat16),
                             jnp.float32)) >= _PAD_F32


def test_f32_sentinel_breaks_reduced_dtypes():
    """The bug this PR fixes: the historical 1e30 sentinel is not usable
    in reduced storage dtypes directly."""
    with np.errstate(over="ignore"):
        assert np.isinf(np.float16(_PAD_F32))          # overflow
    assert float(jnp.asarray(_PAD_F32, jnp.bfloat16)) != _PAD_F32  # rounds


# ----------------------------------- reduced-precision top-k / self-mask

def _assert_selection(D, k, chunk):
    Z, S = lc.streaming_smallest_k(D, k, chunk=chunk)
    Zr, Sr = lc.smallest_k(D, k)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(Sr))
    np.testing.assert_array_equal(np.asarray(Z, np.float32),
                                  np.asarray(Zr, np.float32))
    s = np.asarray(S)
    for row in s.reshape(-1, k):
        assert len(set(row.tolist())) == k, f"duplicate winners: {row}"
    z = np.asarray(Z, np.float32)
    assert np.isfinite(z).all()
    assert (np.diff(z, axis=-1) >= 0).all(), "selection not ascending"


def test_streaming_smallest_k_bf16_no_duplicate_winners(rng):
    """Winner-masking regression: extracted entries are masked with the
    bf16-representable sentinel, so a masked winner can never tie its
    way back into the registers — indices stay unique per row even with
    exact bf16 value ties straddling chunk boundaries."""
    vals = rng.uniform(0.0, 4.0, size=(4, 40)).astype(np.float32)
    D = jnp.asarray(vals, jnp.bfloat16)             # rounding mints ties
    assert int((np.asarray(D, np.float32)[:, :, None]
                == np.asarray(D, np.float32)[:, None, :]).sum()) > 160
    _assert_selection(D, k=6, chunk=8)


def test_streaming_smallest_k_bf16_huge_costs_below_sentinel(rng):
    """Real costs just below the bf16 sentinel still lose to it: the pad
    columns of a non-multiple chunk never enter the winner set."""
    pad = pad_dist_for(jnp.bfloat16)
    vals = rng.uniform(0.5, 0.99, size=(2, 20)).astype(np.float32) * pad
    D = jnp.asarray(vals, jnp.bfloat16)
    Z, S = lc.streaming_smallest_k(D, 4, chunk=8)   # pads 20 -> 24
    assert int(np.asarray(S).max()) < 20, "pad column selected as winner"
    assert float(np.asarray(Z, np.float32).max()) < pad
    _assert_selection(D, k=4, chunk=8)


def test_streaming_smallest_k_f16_stays_finite(rng):
    """float16: the historical 1e30 mask is inf here; the dtype-derived
    sentinel keeps every register finite and the selection exact."""
    D = jnp.asarray(rng.uniform(0.0, 100.0, size=(3, 30)), jnp.float16)
    _assert_selection(D, k=5, chunk=8)


def test_mask_self_bf16_saturation_tiebreak():
    """A row whose scores saturated to finfo(bfloat16).max must still
    never retrieve itself: the mask is written in float32, strictly
    above every finite bf16 value."""
    sat = float(jnp.finfo(jnp.bfloat16).max)
    scores = jnp.full((4, 4), sat, jnp.bfloat16)
    scores = scores.at[jnp.arange(4), (jnp.arange(4) + 1) % 4].set(0.5)
    masked = retrieval._mask_self(scores)
    assert masked.dtype == jnp.float32
    diag = np.diag(np.asarray(masked))
    off = np.asarray(masked)[~np.eye(4, dtype=bool)]
    assert (diag > off.max()).all(), "self tied with saturated entries"
    _, idx = jax.lax.top_k(-masked, 1)
    assert not (np.asarray(idx)[:, 0] == np.arange(4)).any(), \
        "top-1 retrieved self on a saturated bf16 row"


def test_mask_self_f32_passthrough_bit_unchanged(rng):
    scores = jnp.asarray(rng.uniform(size=(5, 5)), jnp.float32)
    masked = np.asarray(retrieval._mask_self(scores))
    np.testing.assert_array_equal(masked[~np.eye(5, dtype=bool)],
                                  np.asarray(scores)[~np.eye(5, dtype=bool)])


# ------------------------------------------------------ policy threading

def test_policy_presets():
    assert POLICIES["f32"] == PrecisionPolicy("f32", "float32", "float32",
                                              "float32")
    assert POLICIES["bf16"].storage == "bfloat16"
    assert POLICIES["bf16"].compute == "float32"
    assert POLICIES["bf16_agg"].compute == "bfloat16"
    for p in POLICIES.values():
        assert p.accum == "float32", "accumulators are always float32"
    assert resolve("bf16") is POLICIES["bf16"]
    assert resolve(POLICIES["bf16"]) is POLICIES["bf16"]
    with pytest.raises(ValueError, match="unknown precision policy"):
        resolve("f8")
    with pytest.raises(ValueError, match="precision"):
        EngineConfig(method="act", precision="f64")


def test_default_policy_is_bitwise_f32(corpus):
    """precision="f32" must be the identity: bitwise-equal scores to a
    build that never heard of policies (the tier-1 safety property)."""
    qi, qw = corpus.ids[:3], corpus.w[:3]
    base = retrieval.batch_scores(corpus, qi, qw, method="act", iters=2)
    f32 = retrieval.batch_scores(corpus, qi, qw, method="act", iters=2,
                                 precision="f32")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(f32))


@pytest.mark.parametrize("method", ["act", "rwmd", "rwmd_rev", "omr", "ict"])
def test_batch_scores_bf16_within_measured_band(corpus, method):
    qi, qw = corpus.ids[:4], corpus.w[:4]
    f32 = np.asarray(retrieval.batch_scores(corpus, qi, qw, method=method,
                                            iters=2), np.float64)
    bf = np.asarray(retrieval.batch_scores(corpus, qi, qw, method=method,
                                           iters=2, precision="bf16"),
                    np.float64)
    err = np.abs(bf - f32).max()
    assert err < 8e-3, f"{method}: bf16 drift {err} beyond measured band"
    assert err > 0.0, f"{method}: bitwise f32 — precision kwarg dropped"


def test_bf16_policy_preserves_topk_agreement(corpus):
    """recall@k of the bf16 policy vs the f32 ranking on the fixture —
    the micro version of the benched precision-vs-recall frontier."""
    qi, qw = corpus.ids[:8], corpus.w[:8]
    k = 8
    f32 = retrieval.batch_scores(corpus, qi, qw, method="act", iters=2)
    bf = retrieval.batch_scores(corpus, qi, qw, method="act", iters=2,
                                precision="bf16")
    _, ref_idx = jax.lax.top_k(-f32, k)
    _, got_idx = jax.lax.top_k(-bf, k)
    assert retrieval.topl_overlap(got_idx, ref_idx) >= 0.95


# -------------------------------------------------- VMEM layout halving

def test_block_layouts_halve_storage_slabs_under_bf16():
    """The static VMEM model reflects the policy: the Z-ladder storage
    block is exactly half as large under bf16, and a candidate table in
    storage dtype rides as one exact bf16 part instead of float32's
    three, while index/accumulator buffers hold."""
    dims = dict(nq=8, v=2048, h=64, m=32, k=8)
    f32 = kops.block_layout("dist_topk", **dims)
    bf = kops.block_layout("dist_topk", **dims, dtype="bfloat16")
    assert bf.buffer("z").nbytes * 2 == f32.buffer("z").nbytes
    assert bf.buffer("s").nbytes == f32.buffer("s").nbytes
    assert bf.vmem_bytes() < f32.vmem_bytes()

    cdims = dict(nq=8, b=256, h=64, v=2048, qh=64, mode="rev_min",
                 block_n=64)
    f32 = kops.block_layout("cand_dist", **cdims)
    bf = kops.block_layout("cand_dist", **cdims, dtype="bfloat16")
    assert bf.buffer("table").nbytes * 3 == f32.buffer("table").nbytes
    assert bf.buffer("acc").nbytes == f32.buffer("acc").nbytes
    assert bf.vmem_bytes() < f32.vmem_bytes()


def test_vmem_pass_covers_bf16_profiles():
    from repro.analysis import vmem
    labels = [label for label, _, _ in vmem.check_configs()]
    assert any(label.endswith(":bf16") for label in labels), \
        "vmem pass lost its bf16-policy profiles"
    violations, checked = vmem.run()
    assert violations == [] and checked == len(labels)


# ------------------------------------------- checkpoint dtype round-trip

def test_restore_dtype_mismatch_is_typed_error(tmp_path):
    d = str(tmp_path)
    store.save(d, 0, {"x": jnp.ones((3, 2), jnp.bfloat16)})
    with pytest.raises(CheckpointCorrupt, match="dtype mismatch"):
        store.restore(d, 0, {"x": np.zeros((3, 2), np.float32)})
    out = store.restore(d, 0, {"x": jnp.zeros((3, 2), jnp.bfloat16)})
    assert jnp.asarray(out["x"]).dtype == jnp.bfloat16


def test_bf16_policy_index_snapshot_kill_restore(corpus, tmp_path):
    """A bf16-policy index survives snapshot/kill/restore with its
    policy intact and parity-equal scores — no silent upcast on the way
    back in."""
    from repro.serving import EmdServer, ServingPolicy, restore_server

    cfg = EngineConfig(method="act", iters=2, top_l=4, precision="bf16")
    index = EmdIndex.build(corpus, cfg)
    pol = ServingPolicy(ladder=("primary",), max_batch=2, flush_ms=5.0,
                        backoff_ms=0.0, max_retries=1, deadline_ms=10_000.0)
    d = str(tmp_path / "snap")

    async def serve_and_snapshot():
        from repro.serving import snapshot
        async with EmdServer(index, pol) as server:
            res = await server.search(corpus.ids[0], corpus.w[0])
            snapshot(server, d)
            return res

    async def restore_and_serve():
        server = restore_server(d, pol)
        assert server.config.precision == "bf16", \
            "restore dropped the precision policy"
        async with server:
            return await server.search(corpus.ids[0], corpus.w[0])

    before = asyncio.run(serve_and_snapshot())
    after = asyncio.run(restore_and_serve())
    np.testing.assert_array_equal(np.asarray(before.scores),
                                  np.asarray(after.scores))
    np.testing.assert_array_equal(np.asarray(before.indices),
                                  np.asarray(after.indices))


# ------------------------------------------------------- precision lint

def test_precision_lint_clean_on_policy_trace(corpus):
    from repro.analysis import precision_lint
    qi, qw = corpus.ids[:4], corpus.w[:4]

    def step(q_ids, q_w):
        return retrieval.batch_scores(corpus, q_ids, q_w, method="act",
                                      iters=2, precision="bf16")

    out = precision_lint.check_fn("clean:bf16", step, (qi, qw), nq=4,
                                  v=corpus.v, h=corpus.hmax)
    assert out == []


def test_precision_lint_flags_dropped_policy(corpus):
    """An allegedly-bf16 step that traces pure f32 (the kwarg fell off)
    is a loud violation, not a silent width doubling."""
    from repro.analysis import precision_lint
    qi, qw = corpus.ids[:4], corpus.w[:4]

    def step(q_ids, q_w):            # "bf16" case that ignores the policy
        return retrieval.batch_scores(corpus, q_ids, q_w, method="act",
                                      iters=2)

    out = precision_lint.check_fn("seeded:ignored", step, (qi, qw), nq=4,
                                  v=corpus.v, h=corpus.hmax)
    assert len(out) == 1 and "no bfloat16 avals" in out[0].message


def test_precision_lint_flags_f32_handoff(corpus):
    """A trace that downcasts SOMETHING to bf16 but leaves a Phase-1
    handoff f32 is the subtler regression the shape probe catches."""
    from repro.analysis import precision_lint
    qi, qw = corpus.ids[:4], corpus.w[:4]

    def step(q_ids, q_w):
        s = retrieval.batch_scores(corpus, q_ids, q_w, method="act",
                                   iters=2)               # handoffs f32
        # a traced bf16 op of NON-handoff shape: the policy "exists" in
        # the jaxpr, but the handoff arrays themselves stayed f32
        bonus = q_w.astype(jnp.bfloat16).astype(jnp.float32)
        return s + bonus.sum() * 0.0

    out = precision_lint.check_fn("seeded:handoff", step, (qi, qw), nq=4,
                                  v=corpus.v, h=corpus.hmax)
    assert out and any("float32" in v.message for v in out)


def test_step_cases_include_bf16_collective_subjects():
    """The guarded mesh step list carries the bf16 cases whose halved
    all-gather bytes the collectives manifest pins."""
    from repro.launch import search as S
    names = {c.name: c for c in S.step_cases()}
    for name in ("scores:act:dist:bf16", "scores:act:dist:kernels:bf16"):
        assert name in names, name
        assert names[name].precision == "bf16"
        assert names[name].scale_guarded


# ------------------------------------- cross-backend parity (slow, mesh)

@pytest.mark.slow
@pytest.mark.parametrize("policy,atol", [("bf16", 8e-3), ("bf16_agg", 0.4)])
def test_distributed_backend_policy_parity(policy, atol):
    """EngineConfig(precision=...) on backend="distributed" over the
    8-device host mesh matches the single-host engine under the same
    policy at the measured tolerance (subprocess: XLA_FLAGS must be set
    before jax initializes)."""
    import os
    import subprocess
    import sys

    xla = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS=(xla
                          + " --xla_force_host_platform_device_count=8")
               .strip())
    script = f"""
import dataclasses, jax, numpy as np
from repro.api import EmdIndex, EngineConfig
from repro.data.synth import make_text_like

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
corpus, _ = make_text_like(n_docs=24, vocab=64, m=8, doc_len=10, hmax=16)
q_ids, q_w = corpus.ids[:5], corpus.w[:5]
cfg = EngineConfig(method="act", iters=2, backend="distributed",
                   pad_multiple=16, precision={policy!r})
dst = EmdIndex.build(corpus, cfg, mesh=mesh)
ref = EmdIndex.build(corpus, dataclasses.replace(cfg, backend="reference"))
np.testing.assert_allclose(np.asarray(dst.scores(q_ids, q_w)),
                           np.asarray(ref.scores(q_ids, q_w)),
                           rtol=0, atol={atol})
pal = EmdIndex.build(corpus, dataclasses.replace(cfg, backend="pallas"))
np.testing.assert_allclose(np.asarray(pal.scores(q_ids, q_w)),
                           np.asarray(ref.scores(q_ids, q_w)),
                           rtol=0, atol={atol})
print("POLICY PARITY OK")
"""
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=".",
                         timeout=600)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "POLICY PARITY OK" in res.stdout
