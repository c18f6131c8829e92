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


#: mode -> (block_n, rows kwargs): the cascade's default row tile for the
#: narrow ladders, the smallest legal one where the rows are query bins.
CAND_MODES = {
    "pour": (128, dict(k=4, iters=3)),
    "omr": (128, {}),
    "rev_min": (8, {}),
    "ict": (8, {}),
}


@pytest.mark.parametrize("mode", sorted(CAND_MODES))
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_cand_kernels_compile(one_chip, width, mode):
    w = WIDTHS[width]
    block_n, kw = CAND_MODES[mode]
    kw = dict(kw, qh=w.hmax) if mode in DIST_MODES else kw
    b, hp = 512, round_up(w.hmax, LANE)
    vp = round_up(w.vocab, 256)
    rows = table_rows(mode, **kw)
    shapes = [((NQ, b, hp), jnp.int32), ((NQ, b, hp), jnp.float32),
              ((NQ, 3 * rows, vp), jnp.bfloat16)]
    if mode == "rev_min":
        shapes.append(((NQ, rows, 1), jnp.float32))
    _compile(lambda *a: cand_pallas(*a, mode=mode, block_n=block_n,
                                       block_v=256, **kw),
             one_chip, *shapes)


def test_cand_kernel_compiles_under_highest_precision(one_chip):
    """Each contraction's precision comes from its operand dtype
    (``core.precision.matmul_precision``), so a caller's ambient
    "highest" matmul precision does not reach the bf16 one-hot gather:
    Mosaic refuses fp32 contraction on bf16 operands."""
    w = NEWS
    rows = table_rows("pour", k=4, iters=3)
    shapes = [((NQ, 512, 512), jnp.int32), ((NQ, 512, 512), jnp.float32),
              ((NQ, 3 * rows, round_up(w.vocab, 256)), jnp.bfloat16)]
    with jax.default_matmul_precision("highest"):
        _compile(lambda *a: cand_pallas(*a, mode="pour", k=4, iters=3,
                                        block_n=128, block_v=256),
                 one_chip, *shapes)
