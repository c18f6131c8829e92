"""The search layers carry their stable profiler names.

Each layer opens a ``jax.named_scope`` (``core.scopes``) that lands in the
``op_name`` of the compiled program's instructions, where the benchmark's
trace reduction reads it. These tests compile the engines at tiny sizes
and read the scopes back from ``compiled.as_text()``, so a refactor that
moves work out of its layer, or renames a layer, fails here. The host
spans of ``EmdIndex.search`` are read from a CPU profile.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.api import EmdIndex, EngineConfig
from repro.cascade import CascadeSpec, CascadeStage
from repro.cascade import search as cascade_search
from repro.core import lc, scopes
from repro.data.synth import make_text_like

_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?<![\w.])emd\.[\w.]*\w")

SPEC = CascadeSpec(stages=(CascadeStage("wcd", 0.5),
                           CascadeStage("rwmd", 0.3)),
                   rescorer="act", rescorer_iters=3)


@pytest.fixture(scope="module")
def corpus():
    c, _ = make_text_like(n_docs=24, n_classes=4, vocab=96, m=8,
                          doc_len=30, hmax=16, seed=5)
    return c


def op_scopes(compiled) -> list[tuple[str, str, tuple[str, ...]]]:
    """(instruction, opcode, emd. scopes outermost first) of every
    instruction of a compiled program with a whole ``op_name`` (those of
    reduction bodies are relative to the reduction, and never run as
    operations of their own)."""
    out = []
    for line in compiled.as_text().splitlines():
        m, on = _OP.match(line), _OP_NAME.search(line)
        if m and on and on.group(1).startswith("jit("):
            out.append((m.group(1), m.group(2),
                        tuple(_SCOPE.findall(on.group(1)))))
    return out


def innermost(ops) -> dict[str, set[str]]:
    """Opcodes found under each innermost scope."""
    out: dict[str, set[str]] = {}
    for _, kind, path in ops:
        if path:
            out.setdefault(path[-1], set()).add(kind)
    return out


@pytest.mark.parametrize("use_kernels", [False, True])
def test_batched_engine_layers(corpus, use_kernels):
    """Phase 1, the ladder gathers and the pour of the batched ACT engine
    each carry their scope; the gathers sit inside ``emd.phase2``."""
    q, w = corpus.ids[:5], corpus.w[:5]
    compiled = lc.lc_act_scores_batched.lower(
        corpus, q, w, iters=3, use_kernels=use_kernels, block_q=2,
        block_v=32, block_h=8, block_n=8).compile()
    ops = op_scopes(compiled)
    inner = innermost(ops)
    assert set(inner) == {scopes.PHASE1, scopes.PHASE2,
                          scopes.LADDER_GATHER}
    assert "gather" in inner[scopes.LADDER_GATHER]
    assert {p[:2] for _, _, p in ops if p and p[-1] == scopes.LADDER_GATHER
            } == {(scopes.PHASE2, scopes.LADDER_GATHER)}
    # Phase 1 never runs inside Phase 2, nor Phase 2 inside Phase 1.
    for _, _, p in ops:
        assert not (scopes.PHASE1 in p and scopes.PHASE2 in p), p


def test_segmented_pour_layers():
    """Over the segmented row layout, the segment ladder gathers still sit
    under ``(emd.phase2, emd.ladder_gather)``, and the sum of segment
    costs into rows (one more gather) is ``emd.phase2``'s own."""
    rng = np.random.default_rng(5)
    n, hmax, v = 24, 200, 96
    real = np.arange(hmax) < rng.integers(1, hmax + 1, (n, 1))
    wide = lc.Corpus(ids=rng.integers(0, v, (n, hmax)).astype(np.int32),
                     w=(real / real.sum(1, keepdims=True)).astype(np.float32),
                     coords=rng.normal(size=(v, 8)).astype(np.float32))
    segments, _ = lc.segment_rows(wide, 8)
    assert segments.rows.shape[1] > 1            # rows of two segments
    q, w = wide.ids[:5], wide.w[:5]
    kw = dict(iters=3, use_kernels=True, block_q=2, block_v=32, block_h=8,
              block_n=8)
    gathers = {}
    for seg in (None, segments):
        ops = op_scopes(lc.lc_act_scores_batched.lower(
            wide, q, w, segments=seg, **kw).compile())
        gathers[seg is not None] = {p for _, kind, p in ops
                                    if kind == "gather" and scopes.PHASE2
                                    in p}
        for _, _, p in ops:
            assert not (scopes.PHASE1 in p and scopes.PHASE2 in p), p
    assert gathers[False] == {(scopes.PHASE2, scopes.LADDER_GATHER)}
    assert gathers[True] == {(scopes.PHASE2, scopes.LADDER_GATHER),
                             (scopes.PHASE2,)}


@pytest.mark.parametrize("use_kernels", [False, True])
def test_single_query_engine_layers(corpus, use_kernels):
    compiled = lc.lc_act_scores.lower(
        corpus, corpus.ids[0], corpus.w[0], iters=3,
        use_kernels=use_kernels, block_v=32, block_h=8,
        block_n=8).compile()
    inner = innermost(op_scopes(compiled))
    assert set(inner) == {scopes.PHASE1, scopes.PHASE2,
                          scopes.LADDER_GATHER}
    assert "gather" in inner[scopes.LADDER_GATHER]


@pytest.mark.parametrize("use_kernels", [False, True])
def test_cascade_stage_scopes_match_stage_rows(corpus, use_kernels):
    """Every stage of a cascade, and the rescorer, runs under
    ``emd.cascade.<key>`` with the keys of ``stage_rows``; every scoped
    instruction of the program is inside one of them."""
    q, w = corpus.ids[:4], corpus.w[:4]
    keys = cascade_search.stage_keys(SPEC)
    assert keys == tuple(cascade_search.stage_rows(SPEC, corpus.n, 4))
    assert keys == ("stage1.wcd", "stage2.rwmd", "rescore.act")
    compiled = cascade_search._cascade_device.lower(
        corpus, q, w, SPEC, 4, use_kernels=use_kernels, block_q=2,
        block_v=32, block_h=8, block_n=8).compile()
    ops = op_scopes(compiled)
    outer = {p[0] for _, _, p in ops if p}
    assert outer == {scopes.cascade(k) for k in keys}
    # The pruning stage and the rescorer each run Phase 1 and a pour.
    for key in ("stage2.rwmd", "rescore.act"):
        nested = {p[-1] for _, _, p in ops
                  if p and p[0] == scopes.cascade(key)}
        assert {scopes.PHASE1, scopes.PHASE2} <= nested, key


def _host_spans(profile_dir) -> list[tuple[str, float, float]]:
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events
                       if e.name.startswith(scopes.SEARCH))
    return out


@pytest.mark.parametrize("cascade", [None, SPEC])
def test_search_host_spans(corpus, tmp_path, cascade):
    """``EmdIndex.search`` writes ``emd.search`` around its host work, with
    the input checks, the dispatch of the scoring (or cascade) program
    and, for a plain search, the top-l as children."""
    index = EmdIndex.build(corpus, EngineConfig(method="act", iters=3,
                                                top_l=4, cascade=cascade))
    q, w = corpus.ids[:4], corpus.w[:4]
    jax.block_until_ready(index.search(q, w))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(index.search(q, w))
    spans = _host_spans(str(tmp_path))
    names = sorted(n for n, _, _ in spans)
    children = ["emd.search.check", "emd.search.score"]
    if cascade is None:
        children.append("emd.search.topl")
    assert names == sorted(["emd.search"] + children)
    (_, lo, hi), = [s for s in spans if s[0] == "emd.search"]
    assert all(lo <= s <= e <= hi for n, s, e in spans)
