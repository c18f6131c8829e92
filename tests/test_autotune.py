"""Unit tests of the VMEM-driven tile autotuner (``kernels/autotune``).

Everything here is static — enumeration, cache round-trips, and the
``EngineConfig`` resolution policy. Timing (the ``"force"`` tournament)
is exercised only through the admissibility of what it would time: by
construction it can only pick configs ``analysis/vmem.check_launch``
admits, which is the property tested (fixed cases + a hypothesis sweep
when hypothesis is installed)."""
import dataclasses

import pytest

from repro.analysis import vmem
from repro.api import EmdIndex, EngineConfig
from repro.data.synth import make_text_like
from repro.kernels import autotune

# ------------------------------------------------------------ enumeration

FIXED_CASES = (
    ("dist_topk", dict(nq=8, v=2048, h=256, m=64, k=8)),
    ("act_phase2", dict(nq=8, n=4096, h=128, iters=7)),
    ("cand_pour", dict(nq=8, b=256, h=64, v=512, k=4, iters=3,
                       mode="pour")),
    ("cand_dist", dict(nq=8, b=256, h=500, v=4096, qh=500, mode="ict")),
)


@pytest.mark.parametrize("family,dims", FIXED_CASES,
                         ids=[f for f, _ in FIXED_CASES])
def test_every_enumerated_config_passes_check_launch(family, dims):
    cfgs = autotune.admissible_configs(family, dims)
    assert cfgs, (family, dims)
    for cfg in cfgs:
        assert vmem.check_launch(f"t:{family}", family, {**dims, **cfg}) \
            == [], (family, cfg)


def test_enumeration_is_deterministic_and_deduped():
    family, dims = FIXED_CASES[0]
    a = autotune.admissible_configs(family, dims)
    b = autotune.admissible_configs(family, dims)
    assert a == b
    # dedup key: the wrappers' clamped effective tiles must be unique
    def eff(cfg):
        return tuple(min(blk, -(-dims[d] // 8) * 8)
                     for (k, d), blk in zip(autotune.FAMILY_KNOBS[family],
                                            [cfg[k] for k, _ in
                                             autotune.FAMILY_KNOBS[family]]))
    effs = [eff(c) for c in a]
    assert len(effs) == len(set(effs))


def test_paper_scale_cand_dist_admits_small_block_n():
    """The acceptance shape: blocked-vocab cand_dist at the 20News paper
    profile (hmax = qh = 500, vocab ~ 69682) must fit the scoped-VMEM
    budget — and only fits with small row tiles, the smallest legal one
    (8 rows) among them; no sub-8 tile is offered, since Mosaic refuses
    those."""
    dims = dict(nq=8, b=256, h=500, v=69682, qh=500, mode="ict")
    cfgs = autotune.admissible_configs("cand_dist", dims)
    assert cfgs, "nothing admissible at the paper profile"
    assert all(c["block_n"] <= 16 for c in cfgs)
    assert any(c["block_n"] == 8 for c in cfgs)
    assert all(c["block_n"] % 8 == 0 and c["block_v"] % 128 == 0
               for c in cfgs)


def test_admissible_configs_hypothesis_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(v=st.integers(1, 512), h=st.integers(1, 128),
               m=st.integers(1, 64), k=st.integers(1, 8),
               nq=st.integers(1, 8))
    def prop(v, h, m, k, nq):
        dims = dict(nq=nq, v=v, h=h, m=m, k=k)
        for cfg in autotune.admissible_configs("dist_topk", dims):
            assert vmem.check_launch("h:dist_topk", "dist_topk",
                                     {**dims, **cfg}) == []
    prop()


# ------------------------------------------------------------- TuneCache

def test_tune_cache_round_trip(tmp_path):
    cache = autotune.TuneCache()
    dims = dict(nq=8, v=2048, h=256, m=64, k=8)
    cache.put("dist_topk", dims, {"block_v": 128, "block_h": 64})
    assert cache.get("dist_topk", dims) == {"block_v": 128, "block_h": 64}
    # shape bucketing: 2048 and 1500 share the next-pow2 bucket
    assert cache.get("dist_topk", dict(dims, v=1500)) \
        == {"block_v": 128, "block_h": 64}
    assert cache.get("dist_topk", dict(dims, v=4096)) is None
    assert cache.get("dist_topk", dims, dtype="bfloat16") is None

    path = tmp_path / "tune.json"
    cache.save(str(path))
    loaded = autotune.TuneCache.load(str(path))
    assert loaded.entries == cache.entries
    assert autotune.TuneCache.from_json(cache.to_json()).entries \
        == cache.entries
    # cold-cache states are empty, not errors
    assert autotune.TuneCache.load(None).entries == {}
    assert autotune.TuneCache.load(str(tmp_path / "no.json")).entries == {}


def test_tune_cached_mode_never_times():
    """``mode="cached"`` must not invoke the timing factory at all — a
    make_run that explodes proves it."""
    def boom(cfg):
        raise AssertionError("cached mode timed a config")
    dims = dict(nq=8, v=256, h=32, m=16, k=4)
    assert autotune.tune("dist_topk", dims, boom, cache=autotune.TuneCache(),
                         mode="cached") is None
    assert autotune.tune("dist_topk", dims, boom, mode="off") is None
    with pytest.raises(ValueError):
        autotune.tune("dist_topk", dims, boom, mode="sometimes")


# ------------------------------------------------- EngineConfig resolution

def _corpus():
    c, _ = make_text_like(n_docs=32, n_classes=4, vocab=96, m=8,
                          doc_len=12, hmax=16, seed=3)
    return c


def test_resolve_config_off_ignores_cache(tmp_path):
    corpus = _corpus()
    path = tmp_path / "tune.json"
    cache = autotune.TuneCache()
    for family, dims in autotune.index_plan(
            corpus, EngineConfig(method="act", iters=2)):
        cache.put(family, dims, {"block_v": 4, "block_h": 4,
                                 "block_n": 4})
    cache.save(str(path))
    cfg = EngineConfig(method="act", iters=2, autotune="off",
                       tune_cache=str(path))
    out, picks = autotune.resolve_config(corpus, cfg)
    assert out is cfg and picks == {}
    idx = EmdIndex.build(corpus, cfg)
    assert idx.tuned_blocks == {}


def test_resolve_config_cached_is_deterministic(tmp_path):
    corpus = _corpus()
    cfg0 = EngineConfig(method="act", iters=2)
    plan = autotune.index_plan(corpus, cfg0)
    assert [f for f, _ in plan] == ["dist_topk", "act_phase2"]
    path = tmp_path / "tune.json"
    cache = autotune.TuneCache()
    cache.put("dist_topk", plan[0][1], {"block_v": 64, "block_h": 32})
    cache.save(str(path))

    cfg = dataclasses.replace(cfg0, autotune="cached",
                              tune_cache=str(path))
    out1, picks1 = autotune.resolve_config(corpus, cfg)
    out2, picks2 = autotune.resolve_config(corpus, cfg)
    assert out1 == out2 and picks1 == picks2      # never times -> stable
    assert out1.block_v == 64 and out1.block_h == 32
    assert picks1 == {"dist_topk": {"block_v": 64, "block_h": 32}}
    # act_phase2 missed the cache: block_n keeps its dataclass default
    assert out1.block_n == cfg0.block_n

    idx = EmdIndex.build(corpus, cfg)
    assert idx.tuned_blocks == picks1
    assert idx.config.block_v == 64


def test_resolve_config_explicit_override_wins(tmp_path):
    corpus = _corpus()
    plan = autotune.index_plan(corpus, EngineConfig(method="act", iters=2))
    path = tmp_path / "tune.json"
    cache = autotune.TuneCache()
    cache.put("dist_topk", plan[0][1], {"block_v": 64, "block_h": 32})
    cache.save(str(path))
    cfg = EngineConfig(method="act", iters=2, autotune="cached",
                       tune_cache=str(path), block_v=128)
    out, picks = autotune.resolve_config(corpus, cfg)
    assert out.block_v == 128                     # explicit knob held
    assert out.block_h == 32                      # default knob replaced
    assert picks == {"dist_topk": {"block_h": 32}}
