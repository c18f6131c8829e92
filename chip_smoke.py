"""Bring-up smoke run of EmdIndex and EmdServer on a TPU.

Generates the ``emd-20news`` deployment at its published widths
(``configs/emd_20news.py``: n=18,828 docs, v=69,682 words, m=300,
hmax=500) from ``--seed`` and drives the main path through the public
API, in one process:

  a. full-corpus LC-ACT (iters=7) search on ``backend="pallas"`` — the
     compiled ``dist_topk`` and ``act_phase2`` kernels — against
     ``backend="reference"``;
  b. the ``fast`` cascade on ``backend="pallas"`` (the fused candidate
     kernels) against the reference-backend cascade;
  c. an ``EmdServer`` over index (a) with the default ladder, answering
     concurrent single-query searches.

``--four-chips`` runs only the mesh path instead: ``backend=
"distributed"`` on a (data=1, model=4) mesh, corpus rows sharded over
"model", for full search and the ``fast`` cascade, against the
single-chip ``pallas`` results.

Matmul precision is the library's own: the default float32 policy
contracts float32 operands at HIGHEST (``core.precision``). Each phase
prints one line (compile seconds, wall seconds, max |diff| against what
it is compared with, peak device bytes); no line is a speed figure. The last line is ``{"ok": true, "device": {...}}``. Without a TPU,
or outside a checkout of the repository, or when any phase fails, the
script exits non-zero without that line.

    python chip_smoke.py [--four-chips] [--seed N]
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_QUERIES = 8
TOP_L = 16
#: Score tolerance of the pallas-vs-reference conformance tests
#: (``tests/test_kernels.py``).
RTOL, ATOL = 1e-5, 1e-6


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def setup():
    """Fail fast without a TPU or without the repository's sources."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX sees {devices[0].platform} "
                           "devices only")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SmokeFailure(f"repository sources not found next to "
                           f"{Path(__file__).name}: {e}") from e
    enable_compile_cache(ROOT)
    return devices


def make_data(seed: int):
    import numpy as np

    from repro.configs.emd_20news import CONFIG
    from repro.data.synth import make_text_like

    corpus, _ = make_text_like(n_docs=CONFIG.n_db, n_classes=20,
                               vocab=CONFIG.vocab, m=CONFIG.dim,
                               doc_len=CONFIG.hmax, hmax=CONFIG.hmax,
                               seed=seed)
    rows = np.random.default_rng(seed).choice(CONFIG.n_db, N_QUERIES,
                                              replace=False)
    return corpus, corpus.ids[rows], corpus.w[rows]


def memory(devices, key: str) -> list[int] | None:
    """``key`` of each device's memory stats, where the backend reports
    it."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or key not in s for s in stats):
        return None
    return [s[key] for s in stats]


def timed_search(index, q_ids, q_w):
    """AOT-compile ``index.search`` as one program, check that it holds
    the Pallas kernels, run it. Returns (scores, idx, compile_s, wall_s,
    hlo)."""
    import contextlib

    import jax
    import numpy as np

    # The index's device arrays go in as arguments: closed over, they
    # would be baked into the executable as constants (hundreds of MB,
    # too large for the persistent compile cache).
    arrays = (index.corpus, index._padded_corpus, index._source)

    def search(arrays, q_ids, q_w):
        corpus, padded, source = arrays
        return dataclasses.replace(index, corpus=corpus, _padded_corpus=padded,
                                   _source=source).search(q_ids, q_w)

    t0 = time.perf_counter()
    # a mesh index's step shards under its mesh: compile under it too
    with (contextlib.nullcontext() if index.mesh is None
          else jax.set_mesh(index.mesh)):
        compiled = jax.jit(search).lower(arrays, q_ids, q_w).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    check('custom_call_target="tpu_custom_call"' in hlo,
          f"{index!r}: the compiled search step holds no Pallas kernel")
    t0 = time.perf_counter()
    scores, idx = jax.block_until_ready(compiled(arrays, q_ids, q_w))
    wall_s = time.perf_counter() - t0
    return np.asarray(scores), np.asarray(idx), compile_s, wall_s, hlo


def reference_search(corpus, config, q_ids, q_w):
    """The plain float32 jnp engines (one query per block: the
    reference's Phase-2 gathers are unfused)."""
    import jax
    import numpy as np

    from repro.api import EmdIndex

    ref = EmdIndex.build(corpus, dataclasses.replace(
        config, backend="reference", block_q=1))
    s, i = jax.block_until_ready(ref.search(q_ids, q_w))
    return np.asarray(s), np.asarray(i)


def topl_diff(name, got, want) -> float:
    """Tie-aware top-l agreement: every returned index equals the
    other's, except at ranks where both scores tie within tolerance.
    Returns max |score diff|."""
    import numpy as np

    (gs, gi), (ws, wi) = got, want
    check(gs.shape == ws.shape == (N_QUERIES, TOP_L),
          f"{name}: shapes {gs.shape} vs {ws.shape}")
    check(bool(np.isfinite(gs).all()), f"{name}: non-finite scores")
    tol = ATOL + RTOL * np.abs(ws)
    diff = np.abs(gs - ws)
    check(bool((diff <= tol).all()),
          f"{name}: scores differ by up to {diff.max():.3g}")
    for q in range(N_QUERIES):
        for r in np.nonzero(gi[q] != wi[q])[0]:
            near = np.abs(ws[q] - ws[q, r]) <= 2 * tol[q, r]
            check(gi[q, r] in wi[q][near],
                  f"{name}: query {q} rank {r}: index {gi[q, r]} vs "
                  f"{wi[q, r]} without a score tie")
    return float(diff.max())


def mismatches(gs, gi, ws, wi) -> list:
    """(query, rank, got index, got score, wanted index, wanted score)
    where two top-l results differ."""
    return [(q, r, int(gi[q, r]), float(gs[q, r]), int(wi[q, r]),
             float(ws[q, r])) for q, r in zip(*(gi != wi).nonzero())]


def report(phase, devices, compile_s, wall_s, max_diff, **extra) -> None:
    fields = dict(phase=phase, compile_s=round(compile_s, 2),
                  wall_s=round(wall_s, 3), max_abs_diff=max_diff,
                  peak_bytes_in_use=memory(devices, "peak_bytes_in_use"),
                  **extra)
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


async def serve(index, q_ids, q_w):
    from repro.serving import EmdServer, ServingPolicy

    # Default ladder; a deadline long enough that no compile or launch
    # time can push a request down it.
    server = EmdServer(index, ServingPolicy(deadline_ms=600_000.0))
    async with server:
        return await asyncio.gather(*(server.search(q_ids[i], q_w[i])
                                      for i in range(N_QUERIES)))


def single_chip(devices, seed: int) -> None:
    import numpy as np

    from repro.api import EmdIndex, EngineConfig

    corpus, q_ids, q_w = make_data(seed)

    # a. full-corpus search
    cfg = EngineConfig(method="act", iters=7, backend="pallas",
                       top_l=TOP_L)
    index = EmdIndex.build(corpus, cfg)
    s, i, c_s, w_s, _ = timed_search(index, q_ids, q_w)
    d = topl_diff("full", (s, i), reference_search(corpus, cfg, q_ids, q_w))
    report("a_full_search", devices, c_s, w_s, d, block_q=cfg.block_q)

    # b. cascade search
    ccfg = EngineConfig(backend="pallas", cascade="fast", top_l=TOP_L)
    cindex = EmdIndex.build(corpus, ccfg)
    cs, ci, c_s, w_s, _ = timed_search(cindex, q_ids, q_w)
    rs, ri = reference_search(corpus, ccfg, q_ids, q_w)
    check(bool((ci == ri).all()), "cascade: pallas top-l differs from the "
          f"reference cascade's at {mismatches(cs, ci, rs, ri)}")
    report("b_cascade_fast", devices, c_s, w_s, float(np.abs(cs - rs).max()))

    # c. server over index (a)
    t0 = time.perf_counter()
    ds, di = (np.asarray(a) for a in index.search(q_ids, q_w))
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = asyncio.run(serve(index, np.asarray(q_ids), np.asarray(q_w)))
    wall_s = time.perf_counter() - t0
    for q, r in enumerate(results):
        check(r.tier == "primary" and not r.degraded and r.retries == 0,
              f"server: request {q} served by tier {r.tier!r} "
              f"(degraded={r.degraded}, retries={r.retries})")
        check(bool((r.scores == ds[q]).all() and (r.indices == di[q]).all()),
              f"server: request {q} is not bit-identical to index.search")
    report("c_server", devices, warm_s, wall_s, 0.0,
           requests=len(results), tier="primary", retries=0)


def kernel_routes(hlo: str) -> dict:
    """{kernel name: "shard_map" | "fallback"} for the Pallas launches of
    a compiled mesh step: a launch inside a ``kernels/partition`` shim
    carries ``shard_map`` in its op name."""
    import re

    routes = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        op = op.group(1) if op else ""
        kernel = re.search(r"(dist_topk|act_phase2|cand)_pallas", op)
        name = kernel.group(0) if kernel else "kernel"
        route = "shard_map" if "shard_map" in op else "fallback"
        if routes.get(name) not in (None, route):
            route = "mixed"
        routes[name] = route
    return routes


def four_chips(devices, seed: int) -> None:
    import numpy as np

    from repro.api import EmdIndex, EngineConfig
    from repro.launch.mesh import make_test_mesh

    check(len(devices) == 4, f"--four-chips needs 4 chips, found "
          f"{len(devices)}")
    corpus, q_ids, q_w = make_data(seed)
    mesh = make_test_mesh(1, 4)
    for phase, kw in (("4chip_full_search", dict(method="act", iters=7)),
                      ("4chip_cascade_fast", dict(cascade="fast"))):
        cfg = EngineConfig(backend="distributed", top_l=TOP_L, **kw)
        dist = EmdIndex.build(corpus, cfg, mesh=mesh)
        corpus_bytes = memory(devices, "bytes_in_use")
        s, i, c_s, w_s, hlo = timed_search(dist, q_ids, q_w)
        routes = kernel_routes(hlo)
        check(routes and all(r == "shard_map" for r in routes.values()),
              f"{phase}: kernels outside their shard_map shims: {routes}")
        pal = EmdIndex.build(corpus, dataclasses.replace(cfg,
                                                         backend="pallas"))
        ps, pi = (np.asarray(a) for a in pal.search(q_ids, q_w))
        d = topl_diff(phase, (s, i), (ps, pi))
        if cfg.cascade is not None:
            check(bool((i == pi).all()), f"{phase}: distributed top-l "
                  "differs from the single-chip pallas cascade's at "
                  f"{mismatches(s, i, ps, pi)}")
        report(phase, devices, c_s, w_s, d,
               bytes_in_use_per_device=corpus_bytes,
               kernels=",".join(f"{k}:{v}" for k, v in
                                sorted(routes.items())))
        del dist, pal


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed backend on a 4-chip "
                         "mesh, against single-chip pallas")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        devices = setup()
        (four_chips if args.four_chips else single_chip)(devices, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
