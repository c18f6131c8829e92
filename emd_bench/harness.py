"""One benchmark run: load, warm up, measure, check, print.

Everything that belongs to one cell is found by name:

* the cell's entry in ``BENCHMARK.json`` names its configuration and
  traffic mix;
* ``configs/<config>.json``: the deployment's sizes, its generator
  (``gen/<generator>.py``), the engine settings and ground distance,
  and ``"rows_over_chips": true`` where the corpus lies over the cell's
  chips, each chip making and holding its own block of rows;
* ``traffic/<traffic>.json``: the mix's parameters and its loop
  (``traffic/<loop>.py``), which runs the window; its ``engine`` may
  name a cascade's candidate source, ``"source": {"kind": ...}``;
* ``limits/<cell>.json``: the limit of each number compared
  (``check.NUMBERS``, or ``check.SOURCED_NUMBERS`` for a sourced
  cascade);
* ``metrics/<metric>.py``: the reader of each per-layer metric;
* ``work/<kernel>.py``: the operations and bytes each kernel needs.

A new cell, configuration, mix or metric is new files and new entries.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import inspect
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(RuntimeError):
    """A run that cannot measure: no accelerator, or a broken cell."""


def load_module(path: Path):
    """Import a module of the benchmark by its file (names may hold
    dots)."""
    if not path.is_file():
        raise Refused(f"{path.relative_to(ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(
        "emd_bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"{path.relative_to(ROOT)} does not exist")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """A workload with everything its files say."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; BENCHMARK.json has "
                      f"{sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return Cell(name=name, chips=w["chips"],
                config=load_json(ROOT / entry["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def engine_of(cell: Cell) -> dict:
    """The engine settings: the configuration's, with the mix's on
    top."""
    return {**cell.config["engine"], **cell.traffic.get("engine", {})}


def compared(cell: Cell) -> tuple:
    """The numbers that decide the cell's ``correct``; refuses a cell
    whose limits file lacks one."""
    from emd_bench import check

    engine = engine_of(cell)
    if "source" in engine and "stages" not in engine:
        raise Refused(f"{cell.name}'s mix names a candidate source but no "
                      "cascade stages for it to feed")
    names = (check.SOURCED_NUMBERS if "source" in engine
             else check.NUMBERS)
    missing = [k for k in names if k not in cell.limits]
    if missing:
        raise Refused(f"limits/{cell.name}.json gives no limit for "
                      f"{missing}")
    return names


def cell_mesh(chips: int):
    """The distributed backend's mesh over a cell's chips: data 1 x
    model ``chips``, rows over ``model``."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1, chips), ("data", "model"))


def source_spec(source: dict):
    """The candidate source a mix names: ``{"kind": <registered kind>,
    <field>: <value>, ...}`` built through the program's registry."""
    from repro.candidates import SOURCES

    fields = dict(source)
    kind = fields.pop("kind", None)
    if kind not in SOURCES:
        raise Refused(f"no candidate source {kind!r}; registered: "
                      f"{sorted(SOURCES)}")
    cls = SOURCES[kind]
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise Refused(f"source {kind!r} has no field {unknown}; its "
                      f"fields: {sorted(known)}")
    try:
        return cls(**fields)
    except ValueError as e:
        raise Refused(f"source {kind!r}: {e}") from e


def require_accelerator(chips: int):
    """The devices of the run; refuses anything but at least ``chips``
    TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX sees {devices[0].platform} devices only")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts compilations and persistent-cache loads while ``active``."""

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.cache_loads = 0

    def __call__(self, event: str, duration: float, **_):
        if not self.active:
            return
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
        elif "cache_retrieval" in event:
            self.cache_loads += 1


_COUNTER = CompileCounter()
_LISTENING = False
_BOOT: list[tuple[str, float]] = []     # (set-up phase, its end) of boot


def _listen():
    global _LISTENING
    if not _LISTENING:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_COUNTER)
        _LISTENING = True


@dataclasses.dataclass
class Window:
    """What a loop's window produced."""
    answers: list            # (pool query, scores (l,), rows (l,))
    attempted: int
    failed: int
    lost: int                # requests never answered
    e2e: dict                # end-to-end values by metric name
    counters: dict           # per-layer inputs the loop measured
    calls: list              # pool queries of each search call, in order


class Run:
    """The state one loop works with."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace = trace
        self.t_start = t_start
        self.config, self.traffic = cell.config, cell.traffic
        self.data = None
        self.index = None
        self.t_window = None
        self.window_s = None
        self.trace_dir = None
        self.log = []
        self.marks = list(_BOOT)
        self.setup_counts = (0, 0)

    def mark(self, phase: str):
        """Ends a phase of set-up (the next begins)."""
        self.marks.append((phase, time.monotonic()))

    def setup_phases(self) -> str:
        """Seconds of each phase of set-up, from process start to the
        window."""
        ends = self.marks + [("warm-up", self.t_window)]
        t, parts = self.t_start, []
        for phase, end in ends:
            parts.append(f"{phase} {end - t:.3f}")
            t = end
        return ", ".join(parts)

    def engine(self) -> dict:
        """The engine settings: the configuration's, with the mix's on
        top."""
        return engine_of(self.cell)

    def build_index(self):
        from repro.api import EmdIndex, EngineConfig
        from repro.cascade import CascadeSpec, CascadeStage
        from repro.core.lc import Corpus

        e = {k: v for k, v in self.engine().items()
             if k not in ("stages", "rescorer", "source")}
        if e.get("cascade"):
            # The cascade the mix states, stage by stage, is the one run,
            # fed by the candidate source it names.
            full = self.engine()
            source = full.get("source")
            e["cascade"] = CascadeSpec(
                stages=tuple(CascadeStage(s[0], s[1], *s[2:])
                             for s in full["stages"]),
                rescorer=full["rescorer"][0],
                rescorer_iters=full["rescorer"][1],
                source=None if source is None else source_spec(source))
        d = self.data
        self.index = EmdIndex.build(Corpus(ids=d.ids, w=d.w,
                                           coords=d.coords),
                                    EngineConfig(**e), mesh=self.mesh(e))
        return self.index

    def mesh(self, engine: dict):
        """The distributed backend's mesh over the cell's own chips, rows
        over ``model`` (data 1 x model ``chips``); None for the
        single-device backends."""
        if engine.get("backend") != "distributed":
            return None
        return cell_mesh(self.cell.chips)

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window: compile counting, the trace when asked,
        and its host-clock bounds."""
        import jax

        _listen()
        self.setup_counts = (_COUNTER.compiles, _COUNTER.cache_loads)
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="emd_bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
        _COUNTER.compiles = _COUNTER.cache_loads = 0
        _COUNTER.active = True
        self.t_window = time.monotonic()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.monotonic() - self.t_window
            _COUNTER.active = False
            if self.trace:
                jax.profiler.stop_trace()

    def note(self, line: str):
        self.log.append(line)
        print(line, file=sys.stderr, flush=True)


def memory_peak(devices) -> int | None:
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return None if None in peaks else int(max(peaks))


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def check_answers(run: Run, win: Window, sample: int, *, passes: int = 6,
                  storage: str = "float32", answers=None) -> dict:
    """The compared numbers over a sample of the window's answers drawn
    from the seed, against the reference (or, with lower ``passes`` or
    ``storage``, of the reference's own control answers)."""
    from emd_bench import check
    from emd_bench.reference import Reference

    answers = win.answers if answers is None else answers
    tie = run.cell.limits.get("score_err", 0.0)
    rng = np.random.default_rng(run.seed & (2**63 - 1))
    pick = rng.choice(len(answers), min(sample, len(answers)),
                      replace=False) if answers else []
    d = run.data
    g = run.config["ground_distance"]
    ref = Reference(d.ids, d.w, d.coords, run.engine(), g["zero_snap"])
    ctl = None
    if passes != 6 or storage != "float32":
        ctl = Reference(d.ids, d.w, d.coords, run.engine(), g["zero_snap"],
                        passes=passes, storage=storage)
    per, cache = [], {}
    n = d.ids.shape[0]
    for i in sorted(int(p) for p in pick):
        q, scores, rows = answers[i]
        if q not in cache:
            full, top, _ = ref.query(d.q_ids[q], d.q_w[q])
            cache[q] = (np.asarray(full), np.asarray(top, np.float64))
        if ctl is not None:
            _, scores, rows = ctl.query(d.q_ids[q], d.q_w[q])
        per.append(check.judge_answer(scores, rows, *cache[q], n, tie))
    return check.combine(per, win.lost)


def per_layer(run: Run, win: Window, reduced, peaks: dict) -> dict:
    """The cell's per-layer metrics that have something to read."""
    from emd_bench.metrics_base import Record

    rec = Record(run=run, win=win, trace=reduced, peaks=peaks)
    out = {}
    for m in run.cell.per_layer:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def rows_mesh(cell: Cell, gen):
    """The mesh whose ``model`` chips hold the corpus rows, for a
    configuration that asks for it (``rows_over_chips``); else None.
    Refuses a layout that cannot hold: one chip, another backend than
    ``distributed``, a generator that cannot shard, or n that is not a
    multiple of chips x the generator's ``chunk``."""
    if not cell.config.get("rows_over_chips"):
        return None
    c, chips = cell.config, cell.chips
    backend = engine_of(cell).get("backend")
    why = None
    if chips < 2:
        why = "the cell has one chip"
    elif backend != "distributed":
        why = f"backend {backend!r} is not distributed"
    elif "mesh" not in inspect.signature(gen.make).parameters:
        why = f"generator {c['generator']['name']!r} cannot shard"
    elif c["n"] % (chips * c["generator"]["chunk"]):
        why = (f"n={c['n']} is not a multiple of chips x chunk = {chips} x "
               f"{c['generator']['chunk']}")
    if why:
        raise Refused(f"{c['name']} asks for rows over chips, but {why}")
    return cell_mesh(chips)


def make_data(cell: Cell, seed: int):
    """The cell's corpus and query pool from ``seed``, on the device: the
    corpus rows over the cell's chips where the configuration says so."""
    import jax

    gen = load_module(BENCH / "gen" / f"{cell.config['generator']['name']}"
                      ".py")
    mesh = rows_mesh(cell, gen)
    over = {} if mesh is None else {"mesh": mesh}
    data = gen.make(cell.config, seed, cell.traffic["pool"], **over)
    jax.block_until_ready((data.ids, data.q_ids))
    return data


def load_loop(cell: Cell):
    """The ``traffic/<loop>.py`` module that runs the cell's window."""
    return load_module(BENCH / "traffic" / f"{cell.traffic['loop']}.py")


def peak_table(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, devices) -> dict:
    """Run one cell on ``devices``; returns the result line's object."""
    from emd_bench import check

    names = compared(cell)
    run = Run(cell, seed, seconds, trace, t_start)
    peaks = peak_table(devices[0].device_kind) if trace else None
    run.data = make_data(cell, seed)
    run.mark("data")
    win = load_loop(cell).run(run)
    run.note(f"setup: {run.setup_phases()} s; compiles={run.setup_counts[0]}"
             f" cache_loads={run.setup_counts[1]}")
    run.note(f"window: {run.window_s:.3f} s, compiles={_COUNTER.compiles} "
             f"cache_loads={_COUNTER.cache_loads} inside the window")
    peak = memory_peak(devices)
    reduced = None
    if trace:
        from emd_bench import trace as tr
        reduced = tr.reduce(tr.load(run.trace_dir))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    metrics = {}
    if trace:
        metrics = per_layer(run, win, reduced, peaks)
    else:
        e2e = dict(win.e2e, setup_s=run.t_window - t_start)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    run.index = None
    gc.collect()
    t0 = time.monotonic()
    numbers = check_answers(run, win, cell.traffic["check_sample"])
    correct, checks = check.verdict(numbers, cell.limits, names)
    run.note(f"reference: {time.monotonic() - t0:.3f} s for "
             f"{min(cell.traffic['check_sample'], len(win.answers))} answers")
    correct = correct and win.failed == 0
    device = dict(device_info(devices), memory_peak_bytes=peak)
    out = {"correct": bool(correct), "attempted": int(win.attempted),
           "failed": int(win.failed), "metrics": metrics, "device": device}
    if trace:
        from emd_bench import trace as tr
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["breakdown"] = tr.breakdown(reduced)
    for k in (k for k in numbers if k not in checks):
        print(f"reading {k}: {numbers[k]!r}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out["checks"] = checks
    return out


def parse(argv):
    ap = argparse.ArgumentParser(
        description="Run one cell of the EMD search benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def boot(workload: str) -> tuple[Cell, list]:
    """The cell and its chips, with the program importable and every
    compiled program kept in the persistent cache."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the program's sources (src/repro) are not in this "
                      "checkout")
    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(workload)
    import jax
    _BOOT.append(("import", time.monotonic()))
    devices = require_accelerator(cell.chips)
    _BOOT.append(("devices", time.monotonic()))
    _COUNTER.active = True
    _listen()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cell, devices


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    try:
        cell, devices = boot(args.workload)
        out = execute(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devices)
    except Refused as e:
        print(f"emd_bench: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0
