"""VMEM-driven tile autotuner for the fused Pallas kernels.

Tile sizes (``block_n`` / ``block_v`` / ``block_h``) decide both whether
a launch FITS and is LEGAL (the scoped-VMEM budget and Mosaic's
(8, 128)-or-full block rule, both in ``analysis/vmem``) and how fast it
runs (arithmetic intensity vs pipeline depth). Rather than hand-tuning,
this module closes the loop over the two artifacts PR 6 made static:

* candidate enumeration — :func:`admissible_configs` sweeps tile
  assignments and keeps only those ``analysis/vmem.check_launch`` admits
  (same clamp/pad arithmetic as the wrappers, evaluated without
  tracing), so no timed config can OOM a core or be refused by the
  chip's compiler;
* timing — :func:`tune` runs a paired-interleaved tournament
  (``benchmarks.common.paired``, the benches' own harness: interleaving
  cancels drift between the incumbent and the challenger) and caches the
  winner in a :class:`TuneCache` keyed by (kernel family, shape bucket,
  dtype) — shapes bucket to the next power of two, so one measurement
  serves the whole bucket.

``EngineConfig`` threads the policy: ``autotune="off"`` (default —
nothing here runs), ``"cached"`` (apply cached winners, never time; a
miss keeps the defaults, so builds are deterministic and cheap), or
``"force"`` (time admissible configs now and overwrite the cache).
Explicit ``block_*`` values always override: only knobs still at their
``EngineConfig`` dataclass defaults are eligible for autotuned
replacement (:func:`resolve_config`).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os

from repro.analysis import vmem
from repro.kernels import ops

#: Per kernel family: the (EngineConfig knob, dim it tiles) pairs the
#: tuner sweeps. Dims absent from a launch's ``dims`` dict are skipped.
FAMILY_KNOBS: dict[str, tuple[tuple[str, str], ...]] = {
    "dist_topk": (("block_v", "v"), ("block_h", "h")),
    "act_phase2": (("block_n", "n"), ("block_h", "h")),
    "cand_pour": (("block_n", "b"),),
    "cand_dist": (("block_n", "b"), ("block_v", "v")),
}

#: Tile candidates per knob. Sub-8 sizes are legal only where they cover
#: a whole (small) dim; ``check_launch`` drops them everywhere else.
CANDIDATE_BLOCKS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bucket(x: int) -> int:
    """Next power of two >= x (>= 1) — the shape-bucketing of cache keys."""
    b = 1
    while b < x:
        b *= 2
    return b


def admissible_configs(family: str, dims: dict, *,
                       budget_bytes: int = vmem.DEFAULT_VMEM_BUDGET_BYTES,
                       ) -> list[dict]:
    """Every tile assignment for ``family`` at ``dims`` that
    ``vmem.check_launch`` admits, deduplicated by the wrappers' clamped
    effective tiles (a 512 block over a 96-wide dim clamps to the same
    launch as 128 — one entry). Deterministic order: ascending tiles."""
    knobs = [(knob, dim) for knob, dim in FAMILY_KNOBS[family]
             if dim in dims]
    out, seen = [], set()
    for combo in itertools.product(CANDIDATE_BLOCKS, repeat=len(knobs)):
        cfg = {knob: blk for (knob, _), blk in zip(knobs, combo)}
        eff = tuple(min(blk, _round_up(dims[dim], 8))
                    for (_, dim), blk in zip(knobs, combo))
        if eff in seen:
            continue
        if vmem.check_launch(f"autotune:{family}", family, {**dims, **cfg},
                             budget_bytes=budget_bytes):
            continue                           # any violation -> rejected
        seen.add(eff)
        out.append(cfg)
    return out


@dataclasses.dataclass
class TuneCache:
    """Winner store: {cache key -> {knob: tile}}. JSON round-trippable so
    a tuning run on real hardware ships as a file."""
    entries: dict = dataclasses.field(default_factory=dict)

    @staticmethod
    def key(family: str, dims: dict, dtype: str = "float32") -> str:
        parts = []
        for k in sorted(dims):
            v = dims[k]
            parts.append(f"{k}={_bucket(v) if isinstance(v, int) else v}")
        return f"{family}|{','.join(parts)}|{dtype}"

    def get(self, family: str, dims: dict,
            dtype: str = "float32") -> dict | None:
        hit = self.entries.get(self.key(family, dims, dtype))
        return dict(hit) if hit is not None else None

    def put(self, family: str, dims: dict, config: dict,
            dtype: str = "float32") -> None:
        self.entries[self.key(family, dims, dtype)] = dict(config)

    def to_json(self) -> str:
        return json.dumps({"version": 1, "entries": self.entries},
                          indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "TuneCache":
        data = json.loads(text)
        return cls(entries=dict(data.get("entries", {})))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str | None) -> "TuneCache":
        """Empty cache when ``path`` is None or missing — a cold cache is
        the normal first-run state, not an error."""
        if path is None or not os.path.exists(path):
            return cls()
        with open(path) as f:
            return cls.from_json(f.read())


def tournament(configs: list[dict], make_run, reps: int = 5) -> dict:
    """Single-elimination paired timing: the incumbent meets each
    challenger in one interleaved ``paired`` bout; the faster (median of
    per-rep ratios) advances. O(len(configs)) bouts, drift-robust."""
    from benchmarks.common import paired

    best = configs[0]
    best_fn = make_run(best)
    for cfg in configs[1:]:
        fn = make_run(cfg)
        _, _, ratio = paired(best_fn, fn, reps)
        if ratio > 1.0:                        # incumbent slower
            best, best_fn = cfg, fn
    return best


def tune(family: str, dims: dict, make_run, *, cache: TuneCache | None = None,
         mode: str = "cached", dtype: str = "float32", reps: int = 5,
         budget_bytes: int = vmem.DEFAULT_VMEM_BUDGET_BYTES) -> dict | None:
    """Resolve the tile config for one launch shape.

    ``make_run(config) -> zero-arg callable`` builds the timed launch for
    a candidate (only invoked when timing actually happens). Returns the
    winning {knob: tile} dict, or ``None`` when ``mode="off"`` /
    ``mode="cached"`` misses / nothing is admissible.
    """
    if mode not in ("off", "cached", "force"):
        raise ValueError(f"unknown autotune mode {mode!r}; "
                         "one of ('off', 'cached', 'force')")
    if mode == "off":
        return None
    if mode == "cached":
        return cache.get(family, dims, dtype) if cache is not None else None
    configs = admissible_configs(family, dims, budget_bytes=budget_bytes)
    if not configs:
        return None
    best = tournament(configs, make_run, reps)
    if cache is not None:
        cache.put(family, dims, best, dtype)
    return best


# ------------------------------------------------------------------ index
# EngineConfig resolution: which launches an EmdIndex build will make and
# what to time them with. Shapes are capped for force-mode timing — the
# cache key still buckets the TRUE shape, only the measurement proxy
# shrinks (a paper-scale act_phase2 gather would need GBs on the host).


_TIME_CAPS = dict(n=4096, v=4096, b=512, nq=8)


def _capped(dims: dict) -> dict:
    return {k: min(v, _TIME_CAPS[k]) if k in _TIME_CAPS else v
            for k, v in dims.items()}


def _runner(family: str, dims: dict):
    """make_run factory for force-mode timing: synthetic inputs at the
    capped shape, fixed seed, jitted wrapper call per candidate config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops

    d = _capped(dims)
    rng = np.random.default_rng(0)

    if family == "dist_topk":
        coords = jnp.asarray(rng.normal(size=(d["v"], d["m"])), jnp.float32)
        qcs = jnp.asarray(rng.normal(size=(d["nq"], d["h"], d["m"])),
                          jnp.float32)

        def make_run(cfg):
            fn = jax.jit(lambda: kops.dist_topk_batched(
                coords, qcs, d["k"], **cfg))
            return fn
        return make_run

    if family == "act_phase2":
        x = jnp.asarray(rng.uniform(size=(d["n"], d["h"])), jnp.float32)
        k = d["iters"] + 1
        zg = jnp.asarray(np.sort(rng.uniform(
            size=(d["nq"], k, d["n"], d["h"])), 1), jnp.float32)
        wg = jnp.asarray(rng.uniform(
            size=(d["nq"], d["iters"], d["n"], d["h"])), jnp.float32)

        def make_run(cfg):
            return jax.jit(lambda: kops.act_phase2_batched(x, zg, wg, **cfg))
        return make_run

    assert family in ("cand_pour", "cand_dist"), family
    idsg = jnp.asarray(rng.integers(0, d["v"], size=(d["nq"], d["b"],
                                                     d["h"])), jnp.int32)
    xg = jnp.asarray(rng.uniform(size=(d["nq"], d["b"], d["h"])),
                     jnp.float32)
    if family == "cand_pour":
        k = d["k"]
        Z = jnp.asarray(np.sort(rng.uniform(size=(d["nq"], d["v"], k)), -1),
                        jnp.float32)
        W = jnp.asarray(rng.uniform(size=(d["nq"], d["v"], d["iters"])),
                        jnp.float32) if d["iters"] else None
        it = d["iters"]

        def make_run(cfg):
            return jax.jit(lambda: kops.cand_pour(idsg, xg, Z, W, it, **cfg))
        return make_run

    dq = jnp.asarray(rng.uniform(size=(d["nq"], d["v"], d["qh"])),
                     jnp.float32)
    qw = jnp.asarray(rng.uniform(size=(d["nq"], d["qh"])), jnp.float32)
    fn_k = kops.cand_ict if dims.get("mode") == "ict" else kops.cand_rev_min

    def make_run(cfg):
        return jax.jit(lambda: fn_k(idsg, xg, dq, qw, **cfg))
    return make_run


def index_plan(corpus, config) -> list[tuple[str, dict]]:
    """The (family, dims) launches an ``EmdIndex.build(corpus, config)``
    can make on its kernel path, in resolution order (first pick of a
    shared knob wins). Candidate families enter only with a cascade."""
    h, plan = corpus.hmax, []
    iters = config.effective_iters
    k = max(2, iters + 1)
    if config.spec.supports_kernels:
        plan.append(("dist_topk", dict(nq=8, v=corpus.v, h=h, m=corpus.m,
                                       k=k)))
        if iters >= 1:
            plan.append(("act_phase2", dict(nq=config.block_q, n=corpus.n,
                                            h=h, iters=iters)))
    if config.cascade is not None:
        b = 256
        plan.append(("cand_pour", dict(nq=config.block_q, b=b, h=h,
                                       v=corpus.v, k=k, iters=max(iters, 1),
                                       mode="pour")))
        plan.append(("cand_dist", dict(nq=config.block_q, b=b, h=h,
                                       v=corpus.v, qh=h, mode="ict")))
    return plan


def resolve_config(corpus, config):
    """Apply the autotune policy to an ``EngineConfig`` at build time.

    Returns ``(config, picks)``: the config with eligible block knobs
    replaced by tuned tiles, and ``{family: {knob: tile}}`` of what was
    applied (recorded by the benches). A knob is eligible only while it
    still equals its dataclass default — an explicit ``block_*`` always
    wins. ``"cached"`` never times (miss -> defaults kept); ``"force"``
    times every plan entry and persists to ``config.tune_cache``."""
    from repro.api.config import EngineConfig

    if config.autotune == "off":
        return config, {}
    cache = TuneCache.load(config.tune_cache)
    defaults = {f.name: f.default for f in dataclasses.fields(EngineConfig)}
    taken: set[str] = set()
    changes: dict = {}
    picks: dict = {}
    for family, dims in index_plan(corpus, config):
        make_run = (_runner(family, dims) if config.autotune == "force"
                    else None)
        pick = tune(family, dims, make_run, cache=cache,
                    mode=config.autotune)
        if not pick:
            continue
        applied = {}
        for knob, tile in pick.items():
            if knob in taken or getattr(config, knob) != defaults[knob]:
                continue
            taken.add(knob)
            changes[knob] = tile
            applied[knob] = tile
        if applied:
            picks[family] = applied
    if config.autotune == "force" and config.tune_cache is not None:
        cache.save(config.tune_cache)
    if changes:
        config = dataclasses.replace(config, **changes)
    return config, picks
