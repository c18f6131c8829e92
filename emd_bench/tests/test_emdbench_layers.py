"""The layer metrics on a small trace: each operation's self time goes to
the ``emd.`` scope of its instruction, read from the HLO of the programs;
XLA's own instructions go to their source's scope, eager top-l programs
to ``emd.topl``; the layers and the time in no layer add up to the busy
time; and a trace of a program with no scopes reads nothing."""
import json
import time
import types
from pathlib import Path

import pytest

import emdbench_tiny as tiny

from emd_bench import harness, layers
from emd_bench import trace as tr
from emd_bench.metrics_base import Record

HERE = Path(__file__).parent
FIXTURE = HERE / "layers_fixture.json"
BATCH = ["phase1.ms_per_query", "ladder_gather.ms_per_query",
         "pour.ms_per_query", "topl.ms_per_query",
         "device.unscoped_pct.batch"]
CASCADE = ["cascade.stage1.ms_per_query", "cascade.stage2.ms_per_query",
           "cascade.rescore.ms_per_query", "device.unscoped_pct.cascade"]


def read(metric: str, rec):
    return harness.load_module(harness.BENCH / "metrics"
                               / f"{metric}.py").read(rec)


def record(trace_file: Path, hlo_texts, queries: int) -> Record:
    raw = json.loads(trace_file.read_text())
    t = tr.Trace(
        devices={int(k): [tr.parse_op(e["text"], e["start_ns"], e["dur_ns"])
                          for e in v] for k, v in raw["devices"].items()},
        spans=[tr.Event(**e) for e in raw["spans"]])
    run = types.SimpleNamespace(layer_scopes=layers.label_scopes(hlo_texts))
    win = types.SimpleNamespace(counters={"queries": queries})
    return Record(run=run, win=win, trace=tr.reduce(t), peaks={})


@pytest.fixture(scope="module")
def fixture():
    raw = json.loads(FIXTURE.read_text())
    return raw, record(FIXTURE, raw["hlo"], raw["queries"])


def test_self_time_per_scope_by_hand(fixture):
    raw, rec = fixture
    got = layers.layer_seconds(rec)
    want = {k: v * 1e-9 for k, v in raw["expect"]["scope_ns"].items()}
    assert got == pytest.approx(want)


def test_layers_and_unscoped_add_up_to_busy(fixture):
    raw, rec = fixture
    q = raw["queries"]
    parts = [read(m, rec) for m in BATCH[:4]]
    unscoped = read("device.unscoped_pct.batch", rec)
    assert parts == pytest.approx(
        [raw["expect"]["ms_per_query"][k]
         for k in ("phase1", "ladder_gather", "pour", "topl")])
    assert unscoped == pytest.approx(raw["expect"]["unscoped_pct"])
    busy_ms = 1e3 * rec.trace.busy_s / q
    assert rec.trace.busy_s == pytest.approx(raw["expect"]["busy_ns"] * 1e-9)
    assert sum(parts) + busy_ms * unscoped / 100 == pytest.approx(busy_ms)


def test_xla_instructions_take_their_source_scope(fixture):
    """A relayout copy and a loop XLA made over a gather's result, neither
    with an ``op_name``, are the gather's; a copy of an argument is in no
    layer; the loop counter's enclosing phase does not win over the
    gather."""
    raw, _ = fixture
    mod = layers.Module(raw["hlo"][0])
    assert mod.scope("copy.34") == "emd.phase2/emd.ladder_gather"
    assert mod.scope("while.77") == "emd.phase2/emd.ladder_gather"
    assert mod.scope("dynamic-update-slice.488") == \
        "emd.phase2/emd.ladder_gather"
    assert mod.scope("copy.25") == ""
    assert mod.scope("pad.46") == "emd.phase2"
    assert "neg.0" not in {*layers.Module(raw["hlo"][2]).executed()}


def test_trace_and_program_text_give_one_label():
    """The trace prints an instruction's operands with their shapes and
    the program text without: both give the label the times are kept
    under."""
    a = tr.parse_op("%fusion.7 = f32[64,2,4]{2,1,0} fusion(f32[2,8,4]{2,1,0}"
                    " %p, s32[64]{0} %b), kind=kCustom", 0, 1)
    b = tr.parse_op("%fusion.7 = f32[64,2,4]{2,1,0} fusion(%p, %b), "
                    "kind=kCustom, metadata={op_name=\"x\"}", 0, 1)
    assert a.label == b.label


def test_a_label_two_programs_scope_differently_is_in_no_layer():
    text = ("HloModule jit_{m}\n\nENTRY %main (p: f32[4]) -> f32[4] {{\n"
            "  %p = f32[4]{{0}} parameter(0), metadata={{op_name=\"p\"}}\n"
            "  ROOT %copy.1 = f32[4]{{0}} copy(%p), "
            "metadata={{op_name=\"jit({m})/{s}copy\"}}\n}}\n")
    scopes, scoped = layers.label_scopes(
        [text.format(m="a", s="emd.phase1/"), text.format(m="b", s="")])
    assert scoped and scopes["copy.1 copy f32[4]{0}"] is None


@pytest.mark.parametrize("metric", BATCH + CASCADE)
def test_a_program_with_no_scopes_reads_nothing(metric):
    """The trace of a program that names no layer (as before the scopes)
    gives no reading, where the old metrics read as they did."""
    rec = record(HERE / "trace_fixture.json", [], 4)
    assert read(metric, rec) is None
    assert read("xla_ops.ms_per_query", rec) is not None


def test_layers_without_a_trace_read_nothing(fixture):
    _, rec = fixture
    untraced = Record(run=rec.run, win=rec.win, trace=None, peaks={})
    assert all(read(m, untraced) is None for m in BATCH + CASCADE)


def test_a_stage_without_device_time_reads_zero(fixture):
    """Scopes exist, but no operation ran under a cascade stage: 0.0."""
    _, rec = fixture
    assert [read(m, rec) for m in CASCADE[:3]] == [0.0, 0.0, 0.0]


@pytest.mark.parametrize("cell,paths", [
    # On the CPU the ladder gather fuses into the pour's input; on the
    # chip it runs on its own (tests/test_tpu_compile.py).
    ("news-act7-batch", {"emd.phase1", "emd.phase2", "emd.topl"}),
    ("news-fast-cascade", {"emd.cascade.stage1.wcd",
                           "emd.cascade.stage2.rwmd/emd.phase2",
                           "emd.cascade.rescore.act/emd.phase2"}),
])
def test_live_programs_of_a_run_name_the_layers(cell, paths):
    """After a tiny run on the CPU, the programs the process holds carry
    the layers the cell's metrics read."""
    c = tiny.tiny_cell(cell)
    run = harness.Run(c, 3, 0.2, False, time.monotonic())
    run.data = harness.make_data(c, 3)
    harness.load_loop(c).run(run)
    scopes, scoped = layers.label_scopes(layers.live_hlo_texts())
    assert scoped
    assert paths <= set(scopes.values())
