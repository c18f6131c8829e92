"""The serving cell's loop on the CPU at tiny sizes: its arrivals, its
latency arithmetic, a whole run, and faults of ``EmdServer`` that have to
come out not correct."""
import asyncio
import contextlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import emdbench_tiny as tiny

from emd_bench import harness

CELL = "news-serve-poisson"
serve = harness.load_module(harness.BENCH / "traffic" / "serve.py")


def test_schedule_is_reproducible_poisson_at_its_rate():
    """The same seed gives the same due times; another seed the same gaps
    in another order. The mean rate is the mix's to 1e-9, the gaps'
    coefficient of variation an exponential's (1) to 0.01, and arrivals
    per second are as dispersed as a Poisson count's (variance over mean
    1) to 0.2."""
    a, b = serve.schedule(40.0, 1000.0, 2**40 + 7), \
        serve.schedule(40.0, 1000.0, 2**40 + 7)
    c = serve.schedule(40.0, 1000.0, 11)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    np.testing.assert_allclose(np.sort(np.diff(a)), np.sort(np.diff(c)))
    gaps = np.diff(a)
    assert a[0] == 0.0 and len(gaps) == 40_000
    assert abs(len(gaps) / a[-1] / 40.0 - 1.0) < 1e-9
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.01
    per_s = np.histogram(a[:-1], bins=np.arange(0.0, 1000.5, 1.0))[0]
    assert abs(per_s.var() / per_s.mean() - 1.0) < 0.2


def test_pool_order_covers_the_pool_each_pass():
    order = serve.pool_order(100, 32, 5)
    np.testing.assert_array_equal(order, serve.pool_order(100, 32, 5))
    for p in range(3):
        assert sorted(order[32 * p:32 * (p + 1)]) == list(range(32))
    assert serve.buckets(16) == [1, 2, 4, 8, 16]
    assert serve.buckets(12) == [1, 2, 4, 8, 12]


class FakeClock:
    """A clock that the loop's sleeps advance, each waking ``lag`` late,
    as a loop held by a launch would."""

    def __init__(self, lag: float):
        self.now, self.lag = 100.0, lag

    def __call__(self):
        return self.now

    async def sleep(self, dt):
        wake = self.now + dt
        await asyncio.sleep(0)
        self.now = max(self.now, wake) + self.lag


def test_latency_runs_from_the_due_time():
    """A request due at t, sent late and answered at t + x, reads x; one
    sent on time reads its service time alone."""
    clock, service = FakeClock(lag=0.3), 0.05

    async def send(k):
        clock.now += service
        return k

    off = asyncio.run(serve.offer(
        np.array([0.0, 0.5, 1.0]), send, clock=clock, sleep=clock.sleep,
        span=lambda name: contextlib.nullcontext(), lost_after_s=1.0))
    np.testing.assert_allclose(off.sent - off.due, [0.0, 0.3])
    np.testing.assert_allclose(off.done - off.due, [0.05, 0.35])
    assert off.results == [0, 1] and off.lost == 0
    assert off.close == pytest.approx(101.0)


def test_unanswered_request_is_lost():
    never = asyncio.Event()

    async def send(k):
        if k == 1:
            await never.wait()
        return k

    off = asyncio.run(serve.offer(
        np.array([0.0, 0.01, 0.02]), send, clock=harness.time.monotonic,
        sleep=asyncio.sleep, span=lambda name: contextlib.nullcontext(),
        lost_after_s=0.2))
    assert off.lost == 1 and off.results[1] is None
    assert np.isnan(off.done[1]) and off.results[0] == 0


def test_serve_cell_runs_end_to_end():
    out = tiny.run_tiny(tiny.tiny_cell(CELL), seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 16 and out["failed"] == 0
    assert set(out["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert out["metrics"]["latency_p50_ms"]["value"] > 0


def test_serve_cell_reads_its_layers():
    """The loop's counters feed the cell's per-layer readers (the
    trace's idle share needs a chip)."""
    from emd_bench.metrics_base import Record

    cell = tiny.tiny_cell(CELL, rate_qps=40.0)
    run = harness.Run(cell, 9, 1.0, False, harness.time.monotonic())
    run.data = harness.make_data(cell, 9)
    win = harness.load_loop(cell).run(run)
    rec = Record(run=run, win=win, trace=None, peaks={})
    got = {m["name"]: harness.load_module(
        harness.BENCH / "metrics" / f"{m['name']}.py").read(rec)
        for m in cell.per_layer}
    assert got["device.idle_pct.serve"] is None
    assert got["server.queue_wait_ms"] > 0
    assert 0 < got["server.batch_fill_pct"] <= 100
    assert got["loadgen.late_ms.p95"] >= 0
    assert got["latency_p95_ms.serve"] >= win.e2e["latency_p50_ms"] > 0
    assert sum(len(c) for c in win.calls) == win.attempted == 40


def _alter_one_answer(resolve):
    """An answer altered where the server resolves it: the first
    request of every launch gets another row in its first place."""
    def broken(self, batch, gen, built, scores, idx, **k):
        idx = np.array(idx)
        idx[0, 0] = (idx[0, 0] + 1) % gen.corpus.n
        return resolve(self, batch, gen, built, scores, idx, **k)
    return broken


def _never_resolve_one(resolve):
    """The first request of every launch in the window is never answered
    (the warm-up's launches, one per bucket, are left alone)."""
    warm = len(serve.buckets(tiny.TRAFFIC["serve"]["max_batch"]))
    launches = []

    def broken(self, batch, gen, built, scores, idx, **k):
        launches.append(len(batch))
        if len(launches) <= warm:
            return resolve(self, batch, gen, built, scores, idx, **k)
        return resolve(self, batch[1:], gen, built, scores[1:], idx[1:],
                       **k)
    return broken


def _drop_half_the_batch(resolve):
    """Each launch's second half answered with the first half's
    answers."""
    def broken(self, batch, gen, built, scores, idx, **k):
        rep = np.arange(len(batch)) % max(1, len(batch) // 2)
        return resolve(self, batch, gen, built, scores[rep], idx[rep], **k)
    return broken


@pytest.mark.parametrize("fault", [_alter_one_answer, _never_resolve_one,
                                   _drop_half_the_batch],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_server_is_not_correct(monkeypatch, fault):
    from repro.serving import EmdServer

    monkeypatch.setattr(EmdServer, "_resolve", fault(EmdServer._resolve))
    out = tiny.run_tiny(tiny.tiny_cell(CELL, rate_qps=100.0,
                                       lost_after_s=1.0))
    assert not out["correct"], out["checks"]


_MESH_CHILD = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path[:0] = [sys.argv[1]]
    import emdbench_tiny as tiny
    from emd_bench import harness
    cell = tiny.tiny_cell("news-act7-batch")
    cell = dataclasses.replace(
        cell, chips=int(sys.argv[2]),
        config=dict(cell.config, engine=dict(cell.config["engine"],
                                             backend="distributed")))
    run = harness.Run(cell, 3, 1.0, False, time.monotonic())
    run.data = harness.make_data(cell, 3)
    index = run.build_index()
    s, r = index.search(run.data.q_ids[:2], run.data.q_w[:2])
    print(json.dumps({"mesh": dict(index.mesh.shape),
                      "devices": index.mesh.devices.size,
                      "rows": list(r.shape)}))
""")


@pytest.mark.parametrize("chips", [1, 4])
def test_distributed_index_meshes_the_cells_chips(chips):
    """``backend="distributed"`` built by the harness gets a data 1 x
    model ``chips`` mesh over the cell's own chips (CPU host devices
    here), not the (1, 1) default."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(tiny.ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", _MESH_CHILD,
                        str(tiny.ROOT / "emd_bench" / "tests"), str(chips)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"mesh": {"data": 1, "model": chips}, "devices": chips,
                   "rows": [2, 16]}


def test_single_device_backends_take_no_mesh():
    run = harness.Run(tiny.load("news-act7-batch"), 1, 1.0, False, 0.0)
    assert run.mesh(run.engine()) is None


def test_knees_of_a_sweep():
    """The highest rate that keeps pace on every seed, and the highest
    that also keeps the seeds' median p95 under four times the lowest
    rate's."""
    from emd_bench import knee

    def row(rate, pace, p95):
        return {"rate_qps": rate, "kept_pace_pct": pace,
                "latency_p95_ms": p95}

    rows = [row(10, 100.1, 80), row(10, 99.8, 90), row(20, 99.2, 300),
            row(20, 100.4, 320), row(30, 98.5, 400), row(30, 99.0, 500),
            row(40, 99.5, 900), row(40, 95.0, 700), row(50, 90.0, 2000)]
    assert knee.knees(rows) == {"knee_qps": 30, "knee_p95_qps": 20}


def test_kept_pace_is_the_second_halfs_answers_over_its_arrivals():
    """Answers of the second half of arrivals over the requests due in it,
    whatever was due or answered before; an answer after the close does
    not count."""
    due = np.array([0.0, 1.0, 2.0, 5.0, 6.0, 7.0, 8.0])
    done = np.array([0.5, 4.9, 5.5, 6.5, 8.5, np.nan, 10.5])
    off = serve.Offered(due=due, sent=due, done=done, results=[None] * 7,
                        lost=1, close=10.0)
    assert serve.kept_pace_pct(off) == pytest.approx(100.0 * 3 / 4)
