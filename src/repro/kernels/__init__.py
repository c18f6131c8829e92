"""Pallas TPU kernels for the paper's compute hot spots.

dist_topk   — fused pairwise-distance + row-top-k (LC-ACT Phase 1).
act_phase2  — fused k-round constrained pour (LC-ACT Phases 2+3) over
              the full corpus.
cand_pour   — per-query candidate reductions for the cascade's compacted
              stages: pour / OMR over ladders gathered by id, reverse-min /
              ICT with an in-kernel one-hot gather.

Written for TPU (pl.pallas_call + BlockSpec VMEM tiling); validated with
interpret=True on CPU. ``ops`` holds the jitted padding wrappers; ``ref``
holds the pure-jnp oracles.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (act_phase2, cand_ict, cand_omr, cand_pour,
                               cand_rev_min, dist_topk)

__all__ = ["ops", "ref", "act_phase2", "cand_ict", "cand_omr", "cand_pour",
           "cand_rev_min", "dist_topk"]
