"""Pallas kernel VMEM static analyzer.

Every kernel family in ``repro.kernels.ops`` publishes its per-grid-cell
block layout as data (``ops.KERNEL_FAMILIES`` / ``ops.block_layout`` —
the same clamp/pad arithmetic the wrappers apply, evaluated without
tracing). This pass turns those layouts into a per-core VMEM footprint:
buffers count at their (8, 128)-tile-padded size, pipelined in/out
blocks twice (Pallas double-buffers the HBM<->VMEM streams), scratch
once, and the total must clear the scoped-VMEM limit every kernel
requests (``kernels/tiling.VMEM_LIMIT_BYTES``). It also validates the
launch geometry: non-empty grids, and every pipelined block legal under
Mosaic's rule (last two dims multiples of (8, 128) or equal to the
array's) — a block interpret mode accepts but the chip's compiler
refuses is a violation here.

Two checked profiles:

* ``bench`` — the shapes the test/bench suites launch, at the default
  knobs.
* ``paper`` — 20News scale (n=18.8k, v=69.7k, h=500) with the candidate
  tiles that fit. The profile is the static half of the tile autotuner
  (``repro.kernels.autotune``): :func:`footprint` is the model it sweeps,
  and ``autotune.admissible_configs`` enumerates only tile choices
  :func:`check_launch` admits. ``cand_pour`` reads one candidate block
  of ladders gathered outside the kernel, (rows, block_n, hmax) with
  rows the ladder's rungs. ``cand_dist`` streams the query's table one
  ``block_v`` slab at a time into a persistent (block_n, rows, hmax)
  gather accumulator, so its residency is tile-sized, not corpus-sized —
  but ``rows`` is the query's h there, which holds it to the smallest
  legal row tile (8) at 20News.
"""
from __future__ import annotations

from repro.analysis.violations import Violation
from repro.kernels import ops, tiling

#: The scoped-VMEM limit every kernel launch requests; callers wanting
#: headroom for Mosaic's own temporaries pass a lower budget.
DEFAULT_VMEM_BUDGET_BYTES = tiling.VMEM_LIMIT_BYTES


def footprint(family: str, **dims) -> tuple[ops.KernelBlocks, int]:
    """(layout, per-core VMEM bytes) of one kernel launch — the static
    cost model the tile autotuner sweeps."""
    layout = ops.block_layout(family, **dims)
    return layout, layout.vmem_bytes()


def check_configs() -> list[tuple[str, str, dict]]:
    """(profile:family label, family, dims) for every checked launch."""
    from repro.configs.emd_20news import CONFIG as PAPER
    from repro.configs.emd_mnist import CONFIG as MNIST

    bench = dict(v=2048, h=64, m=32, k=8, n=4096, b=256, iters=3, qh=64)
    out: list[tuple[str, str, dict]] = [
        ("bench:dist_topk", "dist_topk",
         dict(nq=8, v=bench["v"], h=bench["h"], m=bench["m"], k=bench["k"])),
        ("bench:act_phase2", "act_phase2",
         dict(nq=8, n=bench["n"], h=bench["h"], iters=bench["iters"])),
    ]
    for mode in ("pour", "omr"):
        out.append((f"bench:cand_pour:{mode}", "cand_pour",
                    dict(nq=8, b=bench["b"], h=bench["h"], v=bench["v"],
                         k=bench["k"], iters=bench["iters"], mode=mode,
                         block_n=64)))
    for mode in ("rev_min", "ict"):
        out.append((f"bench:cand_dist:{mode}", "cand_dist",
                    dict(nq=8, b=bench["b"], h=bench["h"], v=bench["v"],
                         qh=bench["h"], mode=mode, block_n=64)))
    # Paper scale: Phase-1/2 tiles are h/n-blocked so the defaults hold,
    # and so does the candidate pour's (block_n rows of a 22-rung ladder).
    k = PAPER.iters + 1
    out += [
        ("paper:dist_topk", "dist_topk",
         dict(nq=8, v=PAPER.vocab, h=PAPER.hmax, m=PAPER.dim, k=k)),
        ("paper:act_phase2", "act_phase2",
         dict(nq=8, n=PAPER.n_db, h=PAPER.hmax, iters=PAPER.iters)),
        ("paper:cand_pour", "cand_pour",
         dict(nq=8, b=512, h=PAPER.hmax, v=PAPER.vocab, k=k,
              iters=PAPER.iters)),
    ]
    # cand_dist at paper scale: the (block_n, rows, hmax) accumulator
    # holds the query's h = 500 bins per candidate row (ict: three 512-row
    # ladders), so block_n stays at the smallest legal row tile.
    for mode in ("rev_min", "ict"):
        out.append((f"paper:cand_dist:{mode}", "cand_dist",
                    dict(nq=8, b=512, h=PAPER.hmax, v=PAPER.vocab,
                         qh=PAPER.hmax, mode=mode, block_n=8)))
    # MNIST's dense h = 784 bins make ICT the largest launch of all: it is
    # what sizes the kernels' scoped-VMEM request.
    out.append(("paper:cand_dist:ict:mnist", "cand_dist",
                dict(nq=8, b=512, h=MNIST.hmax, v=MNIST.vocab, qh=MNIST.hmax,
                     mode="ict", block_n=8)))
    # bf16 storage profile: the same paper-scale launches under the
    # "bf16" precision policy. The table/handoff slabs halve, which is
    # exactly what grows the autotuner's admissible tile space — checked
    # here so a layout change that silently stops honoring ``dtype``
    # fails CI (the footprints must fit with DOUBLED candidate tiles).
    out += [
        ("paper:dist_topk:bf16", "dist_topk",
         dict(nq=8, v=PAPER.vocab, h=PAPER.hmax, m=PAPER.dim, k=k,
              dtype="bfloat16")),
        ("paper:cand_pour:bf16", "cand_pour",
         dict(nq=8, b=512, h=PAPER.hmax, v=PAPER.vocab, k=k,
              iters=PAPER.iters, dtype="bfloat16")),
        ("paper:cand_dist:rev_min:bf16", "cand_dist",
         dict(nq=8, b=512, h=PAPER.hmax, v=PAPER.vocab, qh=PAPER.hmax,
              mode="rev_min", block_n=16, dtype="bfloat16")),
    ]
    return out


def check_launch(label: str, family: str, dims: dict, *,
                 budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
                 ) -> list[Violation]:
    """Validate one launch config: layout builds, grid well-formed,
    blocks legal for Mosaic, footprint under budget."""
    try:
        layout, nbytes = footprint(family, **dims)
    except (ValueError, AssertionError) as e:
        return [Violation("vmem", label, f"invalid launch config: {e}")]
    out: list[Violation] = []
    if not layout.grid or any(g < 1 for g in layout.grid):
        out.append(Violation("vmem", label,
                             f"degenerate grid {layout.grid}"))
    for buf in layout.buffers:
        if any(d < 1 for d in buf.shape) and 0 not in buf.shape:
            out.append(Violation(
                "vmem", label,
                f"buffer {buf.name!r} has a negative dim: {buf.shape}"))
        if buf.tiling_error:
            out.append(Violation(
                "vmem", label,
                f"buffer {buf.name!r} breaks Mosaic's (8, 128)-or-full "
                f"block rule: {buf.tiling_error}"))
    if nbytes > budget_bytes:
        out.append(Violation(
            "vmem", label,
            f"per-core VMEM footprint {nbytes / 2**20:.2f} MiB exceeds "
            f"the {budget_bytes / 2**20:.0f} MiB budget "
            f"(grid {layout.grid}; shrink block_n/block_v/block_h)"))
    return out


def run(*, budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
        configs=None) -> tuple[list[Violation], int]:
    """Check every profiled launch; returns (violations, launches)."""
    configs = check_configs() if configs is None else configs
    out: list[Violation] = []
    for label, family, dims in configs:
        out += check_launch(label, family, dims, budget_bytes=budget_bytes)
    return out, len(configs)
