"""The trace reduction on a small trace: busy time is the union of the
device's operations inside the window span, kernel time is found by
name, Pallas kernels by their custom-call target, and idle gaps go to
the innermost host span around them."""
import json
from pathlib import Path

import pytest

import emdbench_tiny  # noqa: F401  (puts the repository on the path)

from emd_bench import trace as tr

FIXTURE = Path(__file__).with_name("trace_fixture.json")


def load_fixture() -> tr.Trace:
    raw = json.loads(FIXTURE.read_text())
    return tr.Trace(
        devices={int(k): [tr.parse_op(e["text"], e["start_ns"], e["dur_ns"])
                          for e in v]
                 for k, v in raw["devices"].items()},
        spans=[tr.Event(**e) for e in raw["spans"]])


def test_busy_kernel_and_idle_by_hand():
    r = tr.reduce(load_fixture())
    raw = json.loads(FIXTURE.read_text())
    expect = raw["expect"]
    assert r.window_s == pytest.approx(expect["window_s"])
    assert r.busy_s == pytest.approx(expect["busy_s"])
    assert r.kernel_s == pytest.approx(expect["pallas_s"])
    for name, s in expect["kernel_s"].items():
        assert r.time_of(name) == pytest.approx(s)
    assert r.idle_by_span == pytest.approx(expect["idle_by_span"])
    b = tr.breakdown(r)
    assert [k for k, _ in b["device_ops"]][0] == expect["top_op"]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        expect["window_s"] - expect["busy_s"])


def test_self_time_of_nested_operations():
    t = load_fixture()
    own = {e.name: s for e, s in tr.self_times(t.devices[0][1:5])}
    assert own == {"while.5": 50, "fusion.7": 50, "act_phase2_pallas.3": 100}


def test_parse_keeps_name_kind_and_shape():
    e = load_fixture().devices[0][0]
    assert (e.name, e.kind) == ("dist_topk_pallas.1", "custom-call")
    assert e.shape.startswith("(f32[32,69632,8]")
    odd = tr.parse_op("not an instruction", 0, 1)
    assert odd.name == "not an instruction" and odd.kind == ""


def test_only_mosaic_custom_calls_are_kernels():
    """A Pallas kernel is found by its target whatever it is named, and
    XLA's own custom calls (``TopK``) stay XLA's."""
    ops = {e.name: e for e in load_fixture().devices[0]}
    assert ops["dist_topk_pallas.1"].is_kernel
    assert ops["act_phase2_pallas.3"].is_kernel
    assert ops["custom-call.4"].target == "TopK"
    assert not ops["custom-call.4"].is_kernel
    assert not any(ops[k].is_kernel for k in ("while.5", "fusion.7", "sort.2"))


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_a_trace_without_a_window_is_refused():
    t = load_fixture()
    t.spans = [s for s in t.spans if s.name != tr.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tr.reduce(t)
