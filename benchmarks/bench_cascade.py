"""Cascaded prune-and-rescore throughput and recall vs full-corpus ACT.

The acceptance workload of the cascade subsystem: the ``wcd -> rwmd ->
act`` ladder at rescore budgets {1%, 5%, 20%} of n against full-corpus
LC-ACT scoring of the same query batch — each budget measured on the
reference engines AND on the ``use_kernels`` path (``backend="pallas"``:
fused candidate kernels for the pruned stages + rescorer). For each
entry it reports

* recall@l of the cascade's top-l vs the full ACT top-l,
* end-to-end queries/sec (PAIRED interleaved timing vs full scoring, as
  in ``bench_batch``), and
* the rows-scored ladder — the cascade's pruned stages together read
  strictly fewer candidate rows than the n the full scorer reads.

NOTE on the kernel entries off-TPU: without a TPU the kernels run in
interpret mode on the CPU — their queries/sec is a conformance smoke
number, not a perf claim (kernel speed is a TPU measurement; see
PERF.md).

Results append to the CSV stream and land in ``BENCH_cascade.json``
(repo root, override with BENCH_CASCADE_JSON) with a distributed-step
entry (the mesh cascade step with its shard-blocked top-budget, on a
single-device mesh here) carrying the same recall + queries/sec fields.
``BENCH_SMOKE=1`` shrinks everything to CI smoke sizes.

The CORPUS-SIZE SWEEP (``sweep`` in the report) is the candidate-source
subsystem's acceptance axis: at each n in {4k, 64k, 1M} (smoke: {256,
512}) a clustered corpus is searched through ``EmdIndex`` with the
full-scan cascade (the reference ranking AND the qps bar) and with each
sublinear source (``centroid_lsh``, ``cluster_tree``), recording
recall@l vs the full-scan top-l, queries/sec, index build seconds, and
probed rows per query. The full-scan stage 1 reads all n rows, so its
qps falls linearly with n; the sourced entries read only their probed
rows, which is what must show as flat latency and a widening speedup at
1M (recall@16 >= 0.9 is the acceptance bar; ``analysis/bench_check``
enforces both).
"""
from __future__ import annotations

import json
import os
import time

import jax

from benchmarks.common import device_kind, emit, paired, text_corpus, timeit
from repro import cascade
from repro.api import EmdIndex, EngineConfig
from repro.candidates import CentroidLSHSpec, ClusterTreeSpec
from repro.cascade import CascadeSpec, CascadeStage
from repro.data.synth import make_clustered_text

#: Rescore budgets as fractions of n (the acceptance grid).
BUDGETS = (0.01, 0.05, 0.20)

#: The mixed-precision frontier, swept through the cascade at the 5%
#: budget (see ``bench_batch.PRECISION_POLICIES`` for the batched-engine
#: sweep of the same policies).
PRECISION_POLICIES = ("f32", "bf16", "bf16_agg")

#: ACT Phase-2 rounds of both the full-corpus baseline and the rescorer.
ACT_ITERS = 3


def _spec(pct: float) -> CascadeSpec:
    """The acceptance cascade at rescore budget ``pct``: wcd prefetch
    keeping 8x the final budget (capped at the full corpus), rwmd prune
    to ``pct``, ACT rescore. The 8x headroom is what the centroid
    heuristic needs to hold >= 0.95 of the true ACT neighbors (rwmd is a
    near-perfect ACT proxy at these budgets; wcd is the lossy stage)."""
    return CascadeSpec(stages=(CascadeStage("wcd", min(8 * pct, 1.0)),
                               CascadeStage("rwmd", pct)),
                       rescorer="act", rescorer_iters=ACT_ITERS)


def _sizes(smoke: bool) -> dict:
    if smoke:
        return dict(n_docs=64, n_classes=4, vocab=192, m=16, doc_len=24,
                    hmax=16, nq=8, top_l=4, reps=3)
    return dict(n_docs=1024, n_classes=8, vocab=512, m=16, doc_len=20,
                hmax=16, nq=64, top_l=16, reps=7)


def _sweep_plan(smoke: bool) -> list[dict]:
    """Per-n sweep rungs: the full-scan reference ladder (absolute
    budgets so the scan cost is the only thing growing with n) and the
    two sublinear sources sized to the corpus. Every source sets
    ``refine`` to the reference's wcd scan budget: the probed rows are
    re-ranked by exact centroid distance so the downstream rwmd stage
    sees the same wcd-prefix geometry the reference cascade does —
    without it, probed rows outside that prefix crowd true neighbors
    out of the prune budget (~0.10 recall lost at 64k). Probe counts
    target ~12% of buckets (the measured recall@16 >= 0.9 operating
    point); caps carry ~2x headroom over mean occupancy so overflow
    drops stay in the low percent."""
    if smoke:
        return [
            dict(n=256, scan=64, prune=32,
                 lsh=CentroidLSHSpec(n_buckets=16, probes=6, bucket_cap=32,
                                     refine=64),
                 tree=ClusterTreeSpec(branching=4, depth=2, beam=4,
                                      probes=3, leaf_cap=32, refine=64)),
            dict(n=512, scan=128, prune=32,
                 lsh=CentroidLSHSpec(n_buckets=16, probes=6, bucket_cap=64,
                                     refine=128),
                 tree=ClusterTreeSpec(branching=4, depth=2, beam=4,
                                      probes=3, leaf_cap=64, refine=128)),
        ]
    return [
        dict(n=4096, scan=512, prune=128,
             lsh=CentroidLSHSpec(n_buckets=64, probes=8, bucket_cap=128,
                                 refine=512),
             tree=ClusterTreeSpec(branching=8, depth=2, beam=8, probes=6,
                                  leaf_cap=128, refine=512)),
        dict(n=65536, scan=2048, prune=256,
             lsh=CentroidLSHSpec(n_buckets=256, probes=32, bucket_cap=512,
                                 refine=2048),
             tree=ClusterTreeSpec(branching=16, depth=2, beam=16,
                                  probes=16, leaf_cap=512, refine=2048)),
        dict(n=1_000_000, scan=4096, prune=256,
             lsh=CentroidLSHSpec(n_buckets=1024, probes=128,
                                 bucket_cap=2048, refine=4096),
             tree=ClusterTreeSpec(branching=16, depth=2, beam=16,
                                  probes=16, leaf_cap=8192, refine=4096)),
    ]


def _sweep(report: dict, smoke: bool, top_l: int) -> None:
    """The corpus-size sweep: full-scan reference vs each sublinear
    source at every n, through ``EmdIndex.search``."""
    nq = 8 if smoke else 16
    report["sweep"] = []
    for rung in _sweep_plan(smoke):
        n = rung["n"]
        reps = 2 if (smoke or n >= 1_000_000) else 3
        # min_len=20: WCD prefetch (and therefore centroid bucketing)
        # needs documents long enough for centroids to carry topic
        # signal — at zipf-minimum lengths of 4 the wcd rank of true
        # neighbors degrades ~10x and no probe budget recovers it.
        corpus, _ = make_clustered_text(
            n, n_topics=8 if smoke else 64,
            vocab=256 if smoke else 2048, m=16, hmax=32, min_len=20,
            seed=17)
        q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]
        full_spec = CascadeSpec(
            stages=(CascadeStage("wcd", rung["scan"]),
                    CascadeStage("rwmd", rung["prune"])),
            rescorer="act", rescorer_iters=ACT_ITERS)
        entries = []
        t0 = time.perf_counter()
        ref = EmdIndex.build(corpus, EngineConfig(
            method="act", iters=ACT_ITERS, top_l=top_l,
            cascade=full_spec))
        build_ref = time.perf_counter() - t0
        _, ref_idx = ref.search(q_ids, q_w)
        us_ref = timeit(lambda: ref.search(q_ids, q_w), n_iter=reps)
        qps_ref = nq / (us_ref / 1e6)
        emit(f"bench_cascade.sweep.n{n}.full_scan", us_ref,
             f"qps={qps_ref:.1f}")
        entries.append(dict(
            source="full_scan", spec=full_spec.describe(),
            admissible=full_spec.admissible, recall_at_l=1.0,
            top_l=top_l, queries_per_sec=round(qps_ref, 2),
            probed_rows_per_query=n,
            build_seconds=round(build_ref, 2)))
        for key in ("lsh", "tree"):
            src_spec = rung[key]
            spec = CascadeSpec(
                stages=(CascadeStage("rwmd", rung["prune"]),),
                rescorer="act", rescorer_iters=ACT_ITERS,
                source=src_spec)
            t0 = time.perf_counter()
            ix = EmdIndex.build(corpus, EngineConfig(
                method="act", iters=ACT_ITERS, top_l=top_l,
                cascade=spec))
            build_s = time.perf_counter() - t0
            _, idx = ix.search(q_ids, q_w)
            recall = cascade.topk_recall(idx, ref_idx)
            us = timeit(lambda: ix.search(q_ids, q_w), n_iter=reps)
            qps = nq / (us / 1e6)
            emit(f"bench_cascade.sweep.n{n}.{src_spec.kind}", us,
                 f"recall@{top_l}={recall:.3f} qps={qps:.1f} "
                 f"full_qps={qps_ref:.1f}")
            probed = src_spec.probes * ix.source.rows.shape[1]
            entries.append(dict(
                source=src_spec.kind, spec=spec.describe(),
                admissible=spec.admissible,
                recall_at_l=round(recall, 4), top_l=top_l,
                queries_per_sec=round(qps, 2),
                probed_rows_per_query=probed,
                emitted_rows_per_query=ix.source.width,
                dropped_rows=int(ix.source.dropped_rows),
                build_seconds=round(build_s, 2),
                speedup_over_full_scan=round(qps / qps_ref, 2)))
        report["sweep"].append(dict(n=n, nq=nq, entries=entries))


def _precision_sweep(report: dict, corpus, q_ids, q_w, nq: int,
                     top_l: int, reps: int) -> None:
    """The cascade's precision-vs-recall frontier: the acceptance
    cascade at the 5% budget under each precision policy — recall@top_l
    of the policy's retrieved set against the f32 cascade's (delta 0 for
    f32), per-(query, vocab-row) handoff bytes from the storage dtype,
    and measured queries/sec. The reduced policies ride the SAME pruned
    stages and rescorer; only the handoff/table dtypes move."""
    import jax.numpy as jnp

    from repro.core.precision import resolve

    pct = 0.05
    entries = []
    ref_idx = None
    for policy in PRECISION_POLICIES:
        casc = EmdIndex.build(corpus, EngineConfig(
            method="act", iters=ACT_ITERS, top_l=top_l, cascade=_spec(pct),
            precision=policy))
        _, idx = casc.search(q_ids, q_w)
        if ref_idx is None:                          # f32 runs first
            ref_idx = idx
        recall = cascade.topk_recall(idx, ref_idx)
        us = timeit(lambda: casc.search(q_ids, q_w), n_iter=reps)
        qps = nq / (us / 1e6)
        storage = jnp.dtype(resolve(policy).storage)
        emit(f"bench_cascade.precision.{policy}", us,
             f"recall@{top_l}={recall:.4f} qps={qps:.1f}")
        entries.append(dict(
            policy=policy, storage_dtype=storage.name, budget_pct=pct,
            recall_at_l_vs_f32=round(recall, 4),
            recall_delta_vs_f32=round(1.0 - recall, 4),
            handoff_bytes_per_row=storage.itemsize * (2 * ACT_ITERS + 1),
            us_per_call=round(us, 1), queries_per_sec=round(qps, 1)))
    report["precision_sweep"] = dict(budget_pct=pct, nq=nq, top_l=top_l,
                                     entries=entries)


def run() -> None:
    smoke = os.environ.get("BENCH_SMOKE", "0") not in ("0", "")
    sz = _sizes(smoke)
    nq, top_l, reps = sz.pop("nq"), sz.pop("top_l"), sz.pop("reps")
    corpus, _ = text_corpus(**sz, seed=11)
    q_ids, q_w = corpus.ids[:nq], corpus.w[:nq]
    n = corpus.n
    # Tile policy, as in bench_batch: BENCH_TUNE_CACHE applies a
    # TuneCache's winners deterministically; unset keeps the defaults.
    tune_cache = os.environ.get("BENCH_TUNE_CACHE") or None
    autotune = "cached" if tune_cache else "off"
    report = {"bench": "bench_cascade", "smoke": smoke,
              "sizes": dict(sz, nq=nq, top_l=top_l),
              "backend": jax.default_backend(),
              "device_kind": device_kind(),
              "autotune": {"mode": autotune, "tune_cache": tune_cache,
                           "tuned_blocks": {}},
              "full_rows_per_query": n, "entries": []}

    full = EmdIndex.build(corpus, EngineConfig(method="act",
                                               iters=ACT_ITERS,
                                               top_l=top_l))
    _, full_idx = full.search(q_ids, q_w)

    for pct in BUDGETS:
        spec = _spec(pct)
        for use_kernels in (False, True):
            backend = "pallas" if use_kernels else "reference"
            casc = EmdIndex.build(corpus, EngineConfig(
                method="act", iters=ACT_ITERS, top_l=top_l, cascade=spec,
                backend=backend, autotune=autotune, tune_cache=tune_cache))
            if use_kernels:
                report["autotune"]["tuned_blocks"].update(casc.tuned_blocks)
            _, idx = casc.search(q_ids, q_w)
            recall = cascade.topk_recall(idx, full_idx)
            us_full, us_casc, speedup = paired(
                lambda: full.search(q_ids, q_w),
                lambda: casc.search(q_ids, q_w), reps)
            rows = cascade.stage_rows(spec, n, top_l)
            cand_rows = sum(v for k, v in rows.items()
                            if not k.startswith("stage1"))
            qps_casc = nq / (us_casc / 1e6)
            qps_full = nq / (us_full / 1e6)
            tag = ".kernels" if use_kernels else ""
            emit(f"bench_cascade.act.b{int(100 * pct)}pct{tag}", us_casc,
                 f"recall@{top_l}={recall:.3f} qps={qps_casc:.1f} "
                 f"full_qps={qps_full:.1f} speedup={speedup:.2f}x")
            report["entries"].append(dict(
                budget_pct=pct, spec=spec.describe(),
                admissible=spec.admissible, use_kernels=use_kernels,
                recall_at_l=round(recall, 4), top_l=top_l,
                queries_per_sec=round(qps_casc, 1),
                full_queries_per_sec=round(qps_full, 1),
                speedup_over_full=round(speedup, 2),
                rows_scored=rows, candidate_rows_per_query=cand_rows,
                scores_fewer_candidate_rows=bool(cand_rows < n)))

    # Distributed cascade step (single-device mesh: step-latency drift +
    # recall through the shard-blocked top-budget path the host-mesh CI
    # job parity-tests).
    pct = 0.05
    dist = EmdIndex.build(corpus, EngineConfig(
        method="act", iters=ACT_ITERS, top_l=top_l, cascade=_spec(pct),
        backend="distributed", pad_multiple=64))
    _, idx_d = dist.search(q_ids, q_w)
    recall_d = cascade.topk_recall(idx_d, full_idx)
    us = timeit(lambda: dist.search(q_ids, q_w), n_iter=reps)
    qps_d = nq / (us / 1e6)
    emit(f"bench_cascade.act.b{int(100 * pct)}pct.distributed", us,
         f"recall@{top_l}={recall_d:.3f} qps={qps_d:.1f}")
    report["distributed_step"] = dict(
        budget_pct=pct, spec=_spec(pct).describe(),
        recall_at_l=round(recall_d, 4), top_l=top_l,
        queries_per_sec=round(qps_d, 1))

    _precision_sweep(report, corpus, q_ids, q_w, nq, top_l, reps)
    _sweep(report, smoke, top_l)

    path = os.environ.get("BENCH_CASCADE_JSON", "BENCH_cascade.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    run()
