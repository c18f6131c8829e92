"""The fused kernels compile for a TPU v5e at the deployments' widths.

Interpret mode (every other kernel test) runs what the TPU compiler may
refuse: unaligned blocks, lane-padded VMEM overflows, ops Mosaic cannot
lower. These tests hand each ``pallas_call`` to the real Mosaic compiler
for one chip of a described (not attached) v5e topology, at the
``emd-20news`` and ``emd-mnist`` widths, and check the compiled program
holds the kernel. Nothing runs, so nothing here says anything about
results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test collection happens in every
worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.emd_20news import CONFIG as NEWS
from repro.configs.emd_mnist import CONFIG as MNIST
from repro.kernels.cand_pour import DIST_MODES, cand_pallas, table_rows
from repro.kernels.act_phase2 import act_phase2_pallas
from repro.kernels.dist_topk import dist_topk_pallas
from repro.kernels.tiling import LANE, round_up

WIDTHS = {"20news": NEWS, "mnist": MNIST}
NQ = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_dist_topk_compiles(one_chip, width):
    w = WIDTHS[width]
    vp, hp = round_up(w.vocab, 256), round_up(w.hmax, 256)
    _compile(lambda c, q, m: dist_topk_pallas(c, q, m, w.iters + 1),
             one_chip, ((vp, w.dim), jnp.float32),
             ((NQ, hp, w.dim), jnp.float32), ((NQ, 1, hp), jnp.float32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_act_phase2_compiles(one_chip, width):
    w = WIDTHS[width]
    np_, hp = round_up(w.n_db, 256), round_up(w.hmax, 256)
    # MNIST's gathered ladders at 8 queries (28 GB) overflow one chip's
    # HBM; its query block is 2
    nq = NQ if width == "20news" else 2
    _compile(act_phase2_pallas, one_chip, ((np_, hp), jnp.float32),
             ((nq, w.iters + 1, np_, hp), jnp.float32),
             ((nq, w.iters, np_, hp), jnp.float32))


#: Segments S of the segmented row layout (``core.lc.segment_rows``) at
#: the deployments' widths: 128-lane segments of the real bins of each
#: row, from the benchmark generators' length multisets (20News: 71.1 real
#: bins per row over 18,828 rows; MNIST: 150 lit pixels over 15,000),
#: rounded up to the pour's 256-row block.
SEGMENTS = {"20news": 22_272, "mnist": 25_600}


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_act_phase2_segments_compile(one_chip, width):
    """The pour over the segmented layout: (S, 128) rows at block_h 128."""
    w, s = WIDTHS[width], SEGMENTS[width]
    _compile(lambda x, zg, wg: act_phase2_pallas(x, zg, wg, block_n=256,
                                                 block_h=LANE),
             one_chip, ((s, LANE), jnp.float32),
             ((NQ, w.iters + 1, s, LANE), jnp.float32),
             ((NQ, w.iters, s, LANE), jnp.float32))


#: mode -> (block_n, rows kwargs): the cascade's default row tile for the
#: narrow ladders, the smallest legal one where the rows are query bins.
CAND_MODES = {
    "pour": (128, dict(k=4, iters=3)),
    "omr": (128, {}),
    "rev_min": (8, {}),
    "ict": (8, {}),
}


def _cand_shapes(mode, b, hp, vp, **kw):
    """Arguments of ``cand_pallas``: gathered (nq, rows, b, hp) ladders
    for the ladder modes, ids and the split (nq, 3 * rows, vp) table for
    the one-hot modes."""
    rows = table_rows(mode, **kw)
    x = ((NQ, b, hp), jnp.float32)
    if mode not in DIST_MODES:
        return [x, ((NQ, rows, b, hp), jnp.float32)]
    shapes = [x, ((NQ, 3 * rows, vp), jnp.bfloat16), ((NQ, b, hp), jnp.int32)]
    if mode == "rev_min":
        shapes.append(((NQ, rows, 1), jnp.float32))
    return shapes


@pytest.mark.parametrize("mode", sorted(CAND_MODES))
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_cand_kernels_compile(one_chip, width, mode):
    w = WIDTHS[width]
    block_n, kw = CAND_MODES[mode]
    kw = dict(kw, qh=w.hmax) if mode in DIST_MODES else kw
    shapes = _cand_shapes(mode, 512, round_up(w.hmax, LANE),
                          round_up(w.vocab, 256), **kw)
    _compile(lambda *a: cand_pallas(*a, mode=mode, block_n=block_n, **kw),
             one_chip, *shapes)


#: launch -> (candidate rows, ladder kwargs): the reductions of the
#: ``news-fast-cascade`` cell, stage 2 (RWMD, one rung over 7,680 rows)
#: and the rescorer (ACT-3, ten rungs over 1,024), at 20NEWS's 512 slots.
CELL_LADDERS = {
    "stage2": (7680, dict(k=1, iters=0)),
    "rescore": (1024, dict(k=4, iters=3)),
}


@pytest.mark.parametrize("launch", sorted(CELL_LADDERS))
def test_cand_ladder_kernel_compiles_at_cell_widths(one_chip, launch):
    b, kw = CELL_LADDERS[launch]
    hp = round_up(NEWS.hmax, LANE)
    assert table_rows("pour", **kw) == {"stage2": 1, "rescore": 10}[launch]
    _compile(lambda *a: cand_pallas(*a, mode="pour", block_n=256, **kw),
             one_chip, *_cand_shapes("pour", b, hp, 0, **kw))


def test_cand_kernel_compiles_under_highest_precision(one_chip):
    """Each contraction's precision comes from its operand dtype
    (``core.precision.matmul_precision``), so a caller's ambient
    "highest" matmul precision does not reach the bf16 one-hot gather:
    Mosaic refuses fp32 contraction on bf16 operands."""
    w = NEWS
    kw = dict(qh=w.hmax)
    shapes = _cand_shapes("ict", 512, round_up(w.hmax, LANE),
                          round_up(w.vocab, 256), **kw)
    with jax.default_matmul_precision("highest"):
        _compile(lambda *a: cand_pallas(*a, mode="ict", block_n=8, **kw),
                 one_chip, *shapes)


@pytest.fixture
def mosaic_kernels(monkeypatch):
    """The engines' Pallas kernels compiled by Mosaic, not interpreted,
    though this process's backend is the CPU. Traces cached before or
    after in this process are dropped, so none mixes the two."""
    from repro.kernels import ops
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    yield
    jax.clear_caches()


def _kernel_scopes(hlo: str) -> dict[str, set]:
    """The emd. scope paths of each Pallas kernel's custom calls, and of
    the fusions (under ``"fusion"``)."""
    import re
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([a-z_0-9]+?)(?:\.\d+)? = ", line)
        on = re.search(r'op_name="([^"]*)"', line)
        kernel = 'custom_call_target="tpu_custom_call"' in line
        if m and on and (kernel or re.search(r"[\]})] fusion\(", line)):
            path = tuple(re.findall(r"(?<![\w.])emd\.[\w.]*\w", on.group(1)))
            out.setdefault(m.group(1) if kernel else "fusion", set()).add(path)
    return out


def test_compiled_kernels_carry_layer_scopes(one_chip, mosaic_kernels):
    """On the chip the kernels are custom calls: each carries its layer's
    scope (Phase 1, the pour, the cascade stage it scores for), where the
    benchmark's trace reduction reads it."""
    from repro.cascade import CascadeSpec, CascadeStage
    from repro.cascade import search as cascade_search
    from repro.core import lc

    n, v, hmax, m = 512, 512, 64, 8
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((n, hmax), jnp.int32), ((n, hmax), jnp.float32),
        ((v, m), jnp.float32), ((NQ, hmax), jnp.int32),
        ((NQ, hmax), jnp.float32))]
    kw = dict(use_kernels=True, block_q=4, block_v=256, block_h=256)

    def batched(ids, w, coords, q, qw):
        return lc.lc_act_scores_batched(lc.Corpus(ids, w, coords), q, qw,
                                        iters=3, block_n=256, **kw)

    spec = CascadeSpec(stages=(CascadeStage("wcd", 0.4),
                               CascadeStage("rwmd", 0.1)),
                       rescorer="act", rescorer_iters=3)

    def cascade(ids, w, coords, q, qw):
        return cascade_search._cascade_device(
            lc.Corpus(ids, w, coords), q, qw, spec, 16, block_n=128, **kw)

    got = _kernel_scopes(jax.jit(batched).lower(*args).compile().as_text())
    # The ladder gather runs as fusions of its own, ahead of the pour.
    assert ("emd.phase2", "emd.ladder_gather") in got.pop("fusion")
    assert got == {"dist_topk_pallas": {("emd.phase1",)},
                   "act_phase2_pallas": {("emd.phase2",)}}
    got = _kernel_scopes(jax.jit(cascade).lower(*args).compile().as_text())
    # Each candidate kernel's ladders are gathered by id ahead of it, in
    # the stage that scores them.
    fusions = got.pop("fusion")
    for stage in ("emd.cascade.stage2.rwmd", "emd.cascade.rescore.act"):
        assert (stage, "emd.phase2", "emd.ladder_gather") in fusions
    assert got == {"cand_pallas": {("emd.cascade.stage2.rwmd", "emd.phase2"),
                                   ("emd.cascade.rescore.act",
                                    "emd.phase2")}}


def test_compiled_segmented_pour_carries_layer_scopes(one_chip,
                                                      mosaic_kernels):
    """Over the segmented row layout the pour kernel keeps its scope and
    the segment ladder gathers theirs, compiled for the chip."""
    from repro.core import lc

    n, v, hmax, m, s = 512, 512, 300, 8, 1024
    args = [jax.ShapeDtypeStruct(sh, d, sharding=one_chip) for sh, d in (
        ((n, hmax), jnp.int32), ((n, hmax), jnp.float32),
        ((v, m), jnp.float32), ((NQ, hmax), jnp.int32),
        ((NQ, hmax), jnp.float32), ((s, LANE), jnp.int32),
        ((s, LANE), jnp.float32), ((n, 3), jnp.int32))]

    def batched(ids, w, coords, q, qw, seg_ids, seg_w, rows):
        return lc.lc_act_scores_batched(
            lc.Corpus(ids, w, coords), q, qw, iters=3, use_kernels=True,
            block_q=4, block_v=256, block_h=256, block_n=256,
            segments=lc.Segments(seg_ids, seg_w, rows))

    got = _kernel_scopes(jax.jit(batched).lower(*args).compile().as_text())
    assert ("emd.phase2", "emd.ladder_gather") in got.pop("fusion")
    assert got == {"dist_topk_pallas": {("emd.phase1",)},
                   "act_phase2_pallas": {("emd.phase2",)}}
