"""Static-analysis suite tests: each checker pass must (a) run clean on
the repo as it stands and (b) reject a seeded violation of exactly the
invariant it guards. The collective-contract pass needs the 8-device
mesh and lives in tests/test_distributed.py; everything here runs
in-process on one device."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import bench_check, hazards, registry_lint, vmem
from repro.analysis.hlo_collectives import collective_bytes
from repro.analysis.jaxpr_cost import iter_eqns
from repro.cascade import spec as cspec
from repro.core.retrieval import METHODS
from repro.kernels import ops
from repro.launch import search as S


# ---------------------------------------------------------------- registry

def test_registry_lint_clean():
    violations, checked = registry_lint.run()
    assert violations == []
    assert checked > 0


def test_bound_table_rejects_missing_reflexivity():
    def rel(m, i, r, ri):
        if (m, i) == (r, ri) == ("ict", 0):
            return False
        return cspec.is_lower_bound(m, i, r, ri)
    out = registry_lint.check_bound_table(rel)
    assert any("reflexive" in v.message for v in out)


def test_bound_table_rejects_inconsistent_chain_edge():
    # Seed the inverted edge OMR <= RWMD: with RWMD <= OMR still present
    # the pair becomes mutually bounding (antisymmetry breaks), exactly
    # what an accidental tightness-table flip would produce.
    def rel(m, i, r, ri):
        if (m, r) == ("omr", "rwmd"):
            return True
        return cspec.is_lower_bound(m, i, r, ri)
    out = registry_lint.check_bound_table(rel)
    assert any("antisymmetric" in v.message for v in out)


def test_bound_table_rejects_emd_only_bound_in_chain():
    # wcd admitted under an act rescorer would wrongly mark the 'fast'
    # preset admissible.
    def rel(m, i, r, ri):
        if m == "wcd" and r == "act":
            return True
        return cspec.is_lower_bound(m, i, r, ri)
    out = registry_lint.check_bound_table(rel)
    assert any("EMD-only" in v.message for v in out)


def test_method_specs_reject_asymmetric_reverse_link():
    methods = dict(METHODS)
    methods["rwmd"] = dataclasses.replace(METHODS["rwmd"], reverse="omr")
    out = registry_lint.check_method_specs(methods)
    assert any("not symmetric" in v.message for v in out)


def test_method_specs_reject_dead_dist_fn():
    methods = dict(METHODS)
    methods["bow"] = dataclasses.replace(
        METHODS["bow"], dist_fn=METHODS["bow"].fn, batch_fn=None)
    out = registry_lint.check_method_specs(methods)
    assert any("dead code" in v.message for v in out)


def test_presets_reject_admissibility_drift():
    declared = dict(cspec.PRESET_ADMISSIBLE, fast=True)   # wcd stage lies
    out = registry_lint.check_cascade_presets(declared=declared)
    assert any("contradicts" in v.message for v in out)


def test_presets_reject_key_drift():
    declared = dict(cspec.PRESET_ADMISSIBLE)
    declared.pop("tight")
    out = registry_lint.check_cascade_presets(declared=declared)
    assert any("out of sync" in v.message for v in out)


# ----------------------------------------------------------------- hazards

def _specs():
    from repro.analysis.collectives_check import check_workload
    return S.search_input_specs(check_workload(), pad_multiple=8)


def test_hazards_clean_on_all_registry_steps():
    violations, checked = hazards.run()
    assert violations == []
    assert checked == len(S.step_cases())


def test_hazards_flag_host_callback():
    def bad(ids, w, coords, q_ids, q_w):
        s = jnp.sum(w) + jnp.sum(q_w)
        return jax.pure_callback(
            lambda x: np.asarray(x), jax.ShapeDtypeStruct((), jnp.float32),
            s)
    out = hazards.check_fn("seeded", bad, _specs())
    assert any("callback" in v.message for v in out)


def test_hazards_flag_float64_promotion():
    def bad(ids, w, coords, q_ids, q_w):
        return jnp.sum(w) * np.float64(2.0)   # f64 under x64 tracing
    out = hazards.check_fn("seeded", bad, _specs())
    assert any("promotion" in v.message for v in out)


def test_hazards_flag_oversized_constant():
    baked = jnp.zeros((512, 1024), jnp.float32)           # 2 MiB
    def bad(ids, w, coords, q_ids, q_w):
        return jnp.sum(w) + jnp.sum(baked)
    out = hazards.check_fn("seeded", bad, _specs())
    assert any("captured constant" in v.message for v in out)
    # A generous budget accepts the same constant.
    assert hazards.check_fn("seeded", bad, _specs(),
                            max_const_bytes=4 << 20) == []


def test_hazards_run_reports_injected_fn():
    def bad(ids, w, coords, q_ids, q_w):
        return jnp.sum(w) * np.float64(2.0)
    violations, checked = hazards.run(extra_fns={"injected": bad})
    assert checked == len(S.step_cases()) + 1
    assert [v for v in violations if v.subject == "injected"]


# -------------------------------------------------------------------- vmem

def test_vmem_clean_on_checked_profiles():
    violations, checked = vmem.run()
    assert violations == []
    assert checked == len(vmem.check_configs())


def test_vmem_rejects_over_budget_blocks():
    out = vmem.check_launch(
        "seeded", "cand_pour",
        dict(nq=8, b=4096, h=500, v=69_682, k=8, iters=7,
             block_n=4096))
    assert any("exceeds" in v.message for v in out)


def test_vmem_rejects_blocks_mosaic_refuses():
    """A row tile that is neither a multiple of 8 nor the whole padded
    candidate axis is refused, as the chip's compiler refuses it — even
    though it fits the budget and interpret mode would run it."""
    dims = dict(nq=8, b=512, h=500, v=69_682, qh=500, mode="rev_min")
    out = vmem.check_launch("seeded", "cand_dist", {**dims, "block_n": 2})
    assert any("(8, 128)-or-full" in v.message for v in out)
    assert vmem.check_launch("seeded", "cand_dist",
                             {**dims, "block_n": 8}) == []
    # a vocabulary slab must be a lane multiple unless it spans the table
    out = vmem.check_launch("seeded", "cand_dist",
                            dict(nq=8, b=64, h=16, v=4096, qh=16,
                                 block_v=64))
    assert any("(8, 128)-or-full" in v.message for v in out)
    assert vmem.check_launch("seeded", "cand_dist",
                             dict(nq=8, b=64, h=16, v=40, qh=16,
                                  block_v=64)) == []


def test_vmem_rejects_invalid_config():
    out = vmem.check_launch("seeded", "dist_topk",
                            dict(nq=8, v=0, h=64, m=32, k=8))
    assert any("invalid launch config" in v.message for v in out)
    out = vmem.check_launch("seeded", "nope", dict())
    assert any("invalid launch config" in v.message for v in out)


def test_vmem_budget_is_configurable():
    label, family, dims = vmem.check_configs()[0]
    assert vmem.check_launch(label, family, dims) == []
    out = vmem.check_launch(label, family, dims, budget_bytes=1024)
    assert any("exceeds" in v.message for v in out)


def test_block_layout_mirrors_wrapper_clamps():
    # Blocks larger than the (padded) dims clamp exactly like the
    # wrappers: v=10 pads to 16, so block_v=256 -> 16 and one grid step.
    layout = ops.block_layout("dist_topk", nq=2, v=10, h=12, m=4, k=3)
    assert layout.grid == (2, 1, 1)
    assert layout.buffer("coords").shape == (16, 4)
    assert layout.buffer("z").shape == (1, 16, 3)


def test_block_layout_act_ladder_widths():
    layout = ops.block_layout("act_phase2", nq=2, n=64, h=32, iters=3)
    assert layout.buffer("zg").shape[1] == 4           # iters + 1 rungs
    assert layout.buffer("wg").shape[1] == 3           # iters
    assert layout.buffer("x").shape == (64, 32)        # shared over queries


def test_vmem_counts_pipelined_buffers_twice():
    layout = ops.block_layout("dist_topk", nq=2, v=64, h=64, m=8, k=4)
    manual = sum(b.nbytes * (1 if b.role == "scratch" else 2)
                 for b in layout.buffers)
    assert layout.vmem_bytes() == manual
    assert layout.vmem_bytes(pipeline_depth=1) < manual


# ------------------------------------------------------- hlo_collectives

_RING_HLO = """\
HloModule ring

ENTRY %main (p0: f32[32]) -> f32[32] {
  %p0 = f32[32]{0} parameter(0)
  %ag = f32[32]{0} all-gather(f32[8]{0} %p0), replica_groups=[2,4], dimensions={0}
  %ar = f32[32]{0} all-reduce(f32[32]{0} %ag), replica_groups=[1,8], to_apply=%add
  %rs = f32[4]{0} reduce-scatter(f32[32]{0} %ar), replica_groups=[1,8], dimensions={0}
  ROOT %cp = f32[4]{0} collective-permute(f32[4]{0} %rs), source_target_pairs={{0,1}}
}
"""


def test_ring_wire_byte_model():
    got = collective_bytes(_RING_HLO, 8)
    # all-gather: result 32*4 bytes, g=4, 2 groups -> R*(g-1)*groups
    assert got["all-gather"] == 128 * 3 * 2
    # all-reduce: 2*R*(g-1)*groups with g=8, one group
    assert got["all-reduce"] == 2 * 128 * 7
    # reduce-scatter: operand = result*g -> R*g*(g-1)*groups
    assert got["reduce-scatter"] == 16 * 8 * 7
    # collective-permute: R * participants
    assert got["collective-permute"] == 16 * 8


_WHILE_HLO = """\
HloModule looped

%body (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  ROOT %ag = f32[16]{0} all-gather(f32[4]{0} %p), replica_groups=[1,4], dimensions={0}
}

%cond (p: f32[16]) -> pred[] {
  %p = f32[16]{0} parameter(0)
  %limit = s32[] constant(5)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %limit), direction=LT
}

ENTRY %main (p0: f32[16]) -> f32[16] {
  %p0 = f32[16]{0} parameter(0)
  ROOT %w = f32[16]{0} while(f32[16]{0} %p0), condition=%cond, body=%body
}
"""


def test_while_trip_count_multiplies_body_collectives():
    got = collective_bytes(_WHILE_HLO, 4)
    # body all-gather wire = 64*(4-1) = 192, times the trip count 5.
    assert got["all-gather"] == 192 * 5


def test_while_without_recovered_trip_count_counts_once():
    hlo = _WHILE_HLO.replace("constant(5)", "parameter(1)")
    got = collective_bytes(hlo, 4)
    assert got["all-gather"] == 192


#: The CPU emulation of a bf16 all-gather: a fusion upcasts to f32, the
#: collective runs at f32. ``{src}`` is the convert's operand as each XLA
#: text format prints it: typed inline, or a bare reference.
_WIDENED_HLO = """\
HloModule widened

%fused (param_0: {dt}[4]) -> f32[4] {{
  %param_0 = {dt}[4]{{0}} parameter(0)
  ROOT %convert.1 = f32[4]{{0}} convert({src})
}}

ENTRY %main (p0: {dt}[4]) -> f32[16] {{
  %p0 = {dt}[4]{{0}} parameter(0)
  %convert_fusion = f32[4]{{0}} fusion(%p0), kind=kLoop, calls=%fused
  ROOT %ag = f32[16]{{0}} all-gather(%convert_fusion), replica_groups=[1,4], dimensions={{0}}
}}
"""


@pytest.mark.parametrize("inline", [True, False], ids=["typed", "bare"])
@pytest.mark.parametrize("dt,width", [("bf16", 2), ("f32", 4)])
def test_widened_collective_charged_at_semantic_width(inline, dt, width):
    src = f"{dt}[4]{{0}} %param_0" if inline else "%param_0"
    got = collective_bytes(_WIDENED_HLO.format(dt=dt, src=src), 4)
    # 16 elements of the narrow source width, g=4, one group
    assert got["all-gather"] == 16 * width * 3


def test_manifest_bf16_steps_gather_fewer_bytes_than_f32():
    """The pinned profile of every bf16 mesh step undercuts its float32
    twin's all-gather bytes (the narrowing contract of the collectives
    pass), and a seeded full-width bf16 profile is rejected."""
    from repro.analysis import collectives_check as CC
    steps = CC.load_manifest()["steps"]
    cases = S.step_cases()
    assert [c for c in cases if c.precision == "bf16"]
    assert CC.check_narrowing(steps, cases) == []
    seeded = dict(steps)
    seeded["scores:act:dist:kernels:bf16"] = steps["scores:act:dist:kernels"]
    out = CC.check_narrowing(seeded, cases)
    assert [v.subject for v in out] == ["scores:act:dist:kernels:bf16"]


# ------------------------------------------------------------ jaxpr walk

def test_iter_eqns_descends_into_scan_and_while():
    def fn(x):
        def body(c, _):
            return c * 2.0, c
        c, _ = jax.lax.scan(body, x, None, length=3)
        return jax.lax.while_loop(lambda v: jnp.sum(v) < 10.0,
                                  lambda v: v + 1.0, c)
    closed = jax.make_jaxpr(fn)(jnp.ones((4,)))
    prims = {e.primitive.name for e in iter_eqns(closed.jaxpr)}
    assert "scan" in prims and "while" in prims
    assert "mul" in prims        # inside the scan body
    assert "add" in prims        # inside the while body


# ----------------------------------------------------------- step registry

def test_step_cases_unique_and_cover_registry():
    cases = S.step_cases()
    names = [c.name for c in cases]
    assert len(names) == len(set(names))
    methods = {c.method for c in cases if c.kind == "scores"}
    assert methods == set(METHODS)
    assert all(c.engine == "dist"
               for c in cases if c.kind == "cascade")
    guarded = {c.name for c in cases if c.scale_guarded}
    assert "cascade:pinned:dist" in guarded
    assert "search:act:dist" not in guarded      # top_k gathers by design


def test_pinned_cascade_case_is_admissible_with_absolute_budgets():
    case = {c.name: c for c in S.step_cases()}["cascade:pinned:dist"]
    assert case.cascade.admissible
    assert all(isinstance(s.budget, int) for s in case.cascade.stages)


def test_build_step_rejects_unknown_kind():
    case = S.StepCase("bad", "nope", "act", "dist")
    with pytest.raises(AssertionError):
        S.build_step(case, None)


# ------------------------------------------------------------------ bench

def _valid_precision_sweep():
    return {"entries": [
        {"policy": "f32", "recall_delta_vs_f32": 0.0,
         "handoff_bytes_per_row": 28, "queries_per_sec": 10.0},
        {"policy": "bf16", "recall_delta_vs_f32": 0.002,
         "handoff_bytes_per_row": 14, "queries_per_sec": 19.0},
        {"policy": "bf16_agg", "recall_delta_vs_f32": 0.02,
         "handoff_bytes_per_row": 14, "queries_per_sec": 19.0},
    ]}


def test_bench_check_clean_on_valid_artifacts(tmp_path):
    provenance = {"device_kind": "cpu",
                  "autotune": {"mode": "cached", "tune_cache": None,
                               "tuned_blocks": {"cand_dist": {"block_n": 2}}}}
    batch = tmp_path / "b.json"
    batch.write_text(json.dumps({"entries": [
        {"engine": "batched", "queries_per_sec": 10.0},
        {"engine": "distributed", "queries_per_sec": 5.0},
    ], "precision_sweep": _valid_precision_sweep(), **provenance}))
    cascade = tmp_path / "c.json"
    cascade.write_text(json.dumps({
        "precision_sweep": _valid_precision_sweep(),
        "entries": [
            {"recall_at_l": 1.0, "queries_per_sec": 9.0,
             "use_kernels": False},
            {"recall_at_l": 0.97, "queries_per_sec": 12.0,
             "use_kernels": True},
        ],
        "distributed_step": {"recall_at_l": 1.0, "queries_per_sec": 4.0},
        "smoke": False,
        "sweep": [
            {"n": 4096, "entries": [
                {"source": "full_scan", "recall_at_l": 1.0,
                 "queries_per_sec": 5.0},
                {"source": "centroid_lsh", "recall_at_l": 0.95,
                 "queries_per_sec": 20.0},
            ]},
        ],
        **provenance,
    }))
    serve = tmp_path / "s.json"
    serve.write_text(json.dumps(_valid_serve()))
    violations, checked = bench_check.run(batch_path=str(batch),
                                          cascade_path=str(cascade),
                                          serve_path=str(serve))
    assert violations == []
    assert checked == 3


def _valid_serve():
    return {
        "entries": [
            {"offered_qps": 50.0, "n_requests": 8, "completed": 8,
             "served": 8, "shed": 0, "p50_ms": 3.0, "p99_ms": 9.0,
             "tier_mix": {"primary": 8}},
        ],
        "chaos": {"n_requests": 8, "completed": 8, "shed": 1,
                  "tier_mix": {"primary": 5, "wcd": 2, "SHED": 1},
                  "deterministic": True},
    }


def test_bench_check_rejects_seeded_defects(tmp_path):
    batch = tmp_path / "b.json"
    batch.write_text(json.dumps({"entries": [
        {"engine": "batched", "queries_per_sec": 10.0}]}))
    cascade = tmp_path / "c.json"
    cascade.write_text(json.dumps({
        "entries": [{"recall_at_l": 1.4, "queries_per_sec": 9.0,
                     "use_kernels": False}],
        "device_kind": "cpu",
        "autotune": {"mode": "sometimes", "tuned_blocks": {}},
    }))
    serve = tmp_path / "s.json"
    serve.write_text(json.dumps({
        "entries": [
            {"offered_qps": 50.0, "n_requests": 8, "completed": 6,
             "served": 5, "shed": 1, "p50_ms": 12.0, "p99_ms": 9.0,
             "tier_mix": {"primary": 4}},
        ],
        "chaos": {"n_requests": 8, "completed": 8,
                  "deterministic": False},
    }))
    violations, _ = bench_check.run(batch_path=str(batch),
                                    cascade_path=str(cascade),
                                    serve_path=str(serve))
    msgs = "\n".join(v.message for v in violations)
    assert "no device_kind" in msgs             # batch artifact lacks it
    assert "no autotune record" in msgs
    assert "autotune mode 'sometimes'" in msgs  # cascade's bad mode
    assert "no distributed-engine entry" in msgs
    assert "outside [0, 1]" in msgs
    assert "use_kernels both ways" in msgs
    assert "no distributed_step record" in msgs
    assert "p50_ms=12.0 > p99_ms=9.0" in msgs
    assert "completed 6/8" in msgs
    assert "tier_mix totals 4 != served 5" in msgs
    assert "not deterministic" in msgs
    assert "no corpus-size sweep" in msgs       # cascade artifact lacks it
    assert "no precision_sweep" in msgs         # both artifacts lack it


def test_bench_check_precision_sweep_bars(tmp_path):
    """bf16 handoff bytes must be exactly half of f32's and the bf16
    recall delta must stay inside the acceptance band."""
    sweep = _valid_precision_sweep()
    sweep["entries"][1]["handoff_bytes_per_row"] = 28   # bf16 not halved
    sweep["entries"][1]["recall_delta_vs_f32"] = 0.05   # over the bar
    del sweep["entries"][2]["queries_per_sec"]          # missing field
    batch = tmp_path / "b.json"
    batch.write_text(json.dumps({"entries": [
        {"engine": "batched", "queries_per_sec": 10.0},
        {"engine": "distributed", "queries_per_sec": 5.0},
    ], "precision_sweep": sweep, "device_kind": "cpu",
        "autotune": {"mode": "off", "tuned_blocks": {}}}))
    violations, _ = bench_check.run(batch_path=str(batch),
                                    cascade_path=str(tmp_path / "nope"),
                                    serve_path=str(tmp_path / "nope"))
    msgs = "\n".join(v.message for v in violations
                     if v.subject == str(batch))
    assert "are not half of f32's" in msgs
    assert "bf16 recall delta 0.05" in msgs
    assert "missing 'queries_per_sec'" in msgs


def test_bench_check_sweep_acceptance_bar(tmp_path):
    """Full (non-smoke) sweeps must show a sublinear source beating the
    full scan's qps at recall >= 0.9 on the LARGEST rung; smoke sweeps
    are exempt; malformed rungs are flagged individually."""
    def artifact(sweep, smoke):
        return {"entries": [
            {"recall_at_l": 1.0, "queries_per_sec": 9.0,
             "use_kernels": False},
            {"recall_at_l": 1.0, "queries_per_sec": 9.0,
             "use_kernels": True}],
            "distributed_step": {"recall_at_l": 1.0,
                                 "queries_per_sec": 4.0},
            "device_kind": "cpu",
            "autotune": {"mode": "off", "tuned_blocks": {}},
            "smoke": smoke, "sweep": sweep,
            "precision_sweep": _valid_precision_sweep()}

    def check(sweep, smoke=False):
        p = tmp_path / "c.json"
        p.write_text(json.dumps(artifact(sweep, smoke)))
        return "\n".join(v.message
                         for v in bench_check.check_cascade(str(p)))

    good = [{"n": 256, "entries": [
        {"source": "full_scan", "recall_at_l": 1.0,
         "queries_per_sec": 50.0},
        {"source": "cluster_tree", "recall_at_l": 0.99,
         "queries_per_sec": 80.0}]}]
    assert check(good) == ""
    # sublinear slower than the scan at the largest rung: bar missed
    slow = [{"n": 1024, "entries": [
        {"source": "full_scan", "recall_at_l": 1.0,
         "queries_per_sec": 50.0},
        {"source": "centroid_lsh", "recall_at_l": 0.99,
         "queries_per_sec": 30.0}]}]
    assert "acceptance bar" in check(slow)
    # high qps but recall below 0.9: bar missed too
    lossy = [{"n": 1024, "entries": [
        {"source": "full_scan", "recall_at_l": 1.0,
         "queries_per_sec": 50.0},
        {"source": "centroid_lsh", "recall_at_l": 0.6,
         "queries_per_sec": 300.0}]}]
    assert "acceptance bar" in check(lossy)
    # ... but only the LARGEST rung carries the bar, and smoke is exempt
    good_big = [dict(good[0], n=4096)]
    assert "acceptance bar" not in check(slow + good_big)
    assert check(lossy, smoke=True) == ""
    # structural defects per rung
    bad = [{"n": 64, "entries": [
        {"source": "cluster_tree", "recall_at_l": 1.4,
         "queries_per_sec": -3.0}]}]
    msgs = check(bad + good)
    assert "no full_scan reference" in msgs
    assert "outside [0, 1]" in msgs
    assert "not a positive number" in msgs


def test_bench_check_serve_requires_chaos_record(tmp_path):
    serve = tmp_path / "s.json"
    art = _valid_serve()
    del art["chaos"]
    serve.write_text(json.dumps(art))
    out = bench_check.check_serve(str(serve))
    assert any("no chaos record" in v.message for v in out)
    # completion gate: a chaos run that hung a request is a violation
    art = _valid_serve()
    art["chaos"]["completed"] = 7
    serve.write_text(json.dumps(art))
    out = bench_check.check_serve(str(serve))
    assert any("7/8 requests under injected faults" in v.message
               for v in out)


def test_bench_check_reports_missing_artifacts(tmp_path):
    violations, _ = bench_check.run(batch_path=str(tmp_path / "no.json"),
                                    cascade_path=str(tmp_path / "no2.json"),
                                    serve_path=str(tmp_path / "no3.json"))
    assert len(violations) == 3
    assert all("artifact missing" in v.message for v in violations)


# -------------------------------------------------------------------- CLI

def test_cli_runs_fast_passes_clean(capsys):
    from repro.analysis import check
    rc = check.main(["--passes", "registry,vmem"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "PASS registry" in out and "PASS vmem" in out


def test_cli_rejects_unknown_pass():
    from repro.analysis import check
    with pytest.raises(SystemExit):
        check.main(["--passes", "nope"])


def test_cli_fails_on_violation(tmp_path, capsys, monkeypatch):
    from repro.analysis import check
    monkeypatch.chdir(tmp_path)                  # no BENCH_*.json here
    rc = check.main(["--passes", "bench"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL bench" in out
