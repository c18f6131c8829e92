"""Open-loop serving: independent users send one held-out query each to
``EmdServer`` at due times of a Poisson process, and each waits for its
own answer.

Parameters (``traffic/<mix>.json``): ``pool`` held-out queries, asked in
an order drawn from the seed (each pass over the pool a new
permutation); ``rate_qps``, the offered rate; ``max_batch`` of the
``ServingPolicy`` (one rung, ``primary``, its other knobs at their
defaults: with one rung no deadline can degrade a batch, and only a
launch that fails sheds); ``lost_after_s``, how long past the close of
arrivals an unanswered request is awaited before it counts as lost;
``check_sample`` answers compared with the reference. Every quantile the
loop reports is numpy's ``percentile`` with method ``QUANTILE_METHOD``
over every request of the window.

Arrivals: the window offers ``round(rate_qps * seconds)`` requests. Their
gaps are the exponential distribution's quantiles at (i + 1/2) / N,
scaled to mean exactly 1 / rate_qps, in an order drawn from the seed, so
every seed offers the same work over the same time and seeds differ only
in where the bursts fall. Request k is due at the sum of the first k
gaps; arrivals close at the sum of all N, the first due time after the
last request, which is ``--seconds``. Each request is sent as a task of
its own at its due time, whether or not earlier ones were answered.

Latency runs from a request's due time, not its send time, to when its
answer is in host memory (``EmdServer`` fetches the top-l before it
resolves a request), so time the generator spent blocked behind a launch
counts. A request shed, degraded below the primary rung, raising, or
unanswered ``lost_after_s`` after the close counts in ``failed``.

Every bucket the server can launch (1, 2, 4, .., ``max_batch``) is
warmed through the server before the window.
"""
from __future__ import annotations

import asyncio
import time
from typing import NamedTuple

import numpy as np


#: numpy ``percentile`` method of every quantile the loop reports: linear
#: interpolation between the order statistics.
QUANTILE_METHOD = "linear"

#: Longest wait for one warm-up launch, compile included, before the run
#: gives up on a request the server never answered.
WARM_UP_S = 900.0


def schedule(rate_qps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the start of arrivals) of the window's requests,
    and last the close of arrivals."""
    n = max(1, round(rate_qps * seconds))
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p)
    gaps *= n / (rate_qps * gaps.sum())
    rng = np.random.default_rng(seed & (2**63 - 1))
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))])


def buckets(max_batch: int) -> list[int]:
    """The query counts ``EmdServer`` pads a launch to: powers of two
    below ``max_batch``, and ``max_batch``."""
    return sorted({min(1 << i, max_batch)
                   for i in range(max_batch.bit_length() + 1)})


def pool_order(count: int, pool: int, seed: int) -> np.ndarray:
    """The pool query of each of ``count`` requests: successive seeded
    permutations of the pool."""
    rng = np.random.default_rng((seed & (2**63 - 1)) ^ 0x5E7E)
    passes = -(-count // pool)
    return np.concatenate([rng.permutation(pool)
                           for _ in range(passes)])[:count]


class Offered(NamedTuple):
    """Host-clock times of each request: when it was due, sent and
    answered (NaN where never), and what it came back with; the close of
    arrivals."""
    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    results: list          # ServeResult, or the exception it raised
    lost: int
    close: float


async def offer(due, send, *, clock, sleep, span,
                lost_after_s: float) -> Offered:
    """Send request k through ``send(k)`` (a coroutine that returns its
    answer) as a task of its own ``due[k]`` after the call, then wait for
    every answer until ``lost_after_s`` past the close ``due[-1]``.
    ``clock``/``sleep`` are the loop's clock and sleep."""
    n = len(due) - 1
    at = clock() + np.asarray(due, np.float64)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    results = [None] * n

    async def one(k: int):
        sent[k] = clock()
        try:
            results[k] = await send(k)
        except Exception as e:      # shed or failed: the request's answer
            results[k] = e
        done[k] = clock()

    tasks = []
    loop = asyncio.get_running_loop()
    for k in range(n + 1):
        wait = at[k] - clock()
        if wait > 0:
            with span("loadgen.sleep"):
                await sleep(wait)
        if k < n:
            tasks.append(loop.create_task(one(k)))
    with span("drain"):
        _, pending = await asyncio.wait(
            tasks, timeout=max(0.0, at[n] + lost_after_s - clock()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    return Offered(due=at[:n], sent=sent, done=done, results=results,
                   lost=len(pending), close=float(at[n]))


class LaunchLog:
    """``EmdServer``'s launch hook: each successful launch's host-clock
    start, its real queries (rows with weight) and bucket, and its
    host-clock time (for the traced run's note), under the span
    ``bench.launch``."""

    def __init__(self, clock, span):
        self.clock, self.span = clock, span
        self.clear()

    def clear(self):
        self.start, self.size, self.bucket, self.ms = [], [], [], []

    def __call__(self, launch, tier, q_ids, q_w):
        t = self.clock()
        with self.span("launch"):
            out = launch(tier, q_ids, q_w)
        self.ms.append((self.clock() - t) * 1e3)
        self.start.append(t)
        self.size.append(int(np.count_nonzero(np.any(q_w != 0, axis=1))))
        self.bucket.append(int(q_w.shape[0]))
        return out


def carrier_starts(log: LaunchLog, count: int) -> np.ndarray | None:
    """Start of the launch that carried each request. The server takes
    its queue first in, first out, and request k is enqueued k-th, so
    launch j carries the next ``size[j]`` requests; None where the sizes
    do not add up to ``count``."""
    if sum(log.size) != count:
        return None
    return np.repeat(np.asarray(log.start, np.float64), log.size)


def kept_pace_pct(off: Offered) -> float:
    """Answers that reached the host in the second half of arrivals over
    the requests due in it, in %: about 100 while the server keeps pace,
    whatever the window's length, and the served rate over the offered
    one where a backlog grows all through the window."""
    half = (off.due[0] + off.close) / 2
    due = int(np.sum(off.due >= half))
    done = int(np.sum((off.done >= half) & (off.done <= off.close)))
    return 100.0 * done / due if due else float("nan")


def summarize(off: Offered, log: LaunchLog, stats) -> dict:
    """The loop's end-to-end values and per-layer counters."""
    from repro.serving import ServeResult

    ok = np.array([isinstance(r, ServeResult) and not r.degraded
                   for r in off.results], bool)
    answered = ~np.isnan(off.done)
    lat = (off.done - off.due)[answered] * 1e3
    late = (off.sent - off.due)[~np.isnan(off.sent)] * 1e3

    def q(values, p):
        return float(np.percentile(values, p, method=QUANTILE_METHOD)) \
            if len(values) else float("nan")

    counters = {"queries": int(ok.sum()), "late_ms_p95": q(late, 95),
                "launches": len(log.start),
                "kept_pace_pct": kept_pace_pct(off)}
    carried = carrier_starts(log, len(off.due))
    if carried is not None:
        counters["queue_wait_ms"] = q((carried - off.due) * 1e3, 50)
    slots = sum(b * c for b, c in stats.bucket_launches.items())
    if slots:
        counters["batch_fill_pct"] = \
            100.0 * sum(stats.tier_served.values()) / slots
    e2e = {"latency_p50_ms": q(lat, 50), "latency_p95_ms": q(lat, 95)}
    return dict(e2e=e2e, counters=counters,
                failed=int((~ok).sum()))


def run(run):
    from repro.serving import EmdServer, ServerStats, ServingPolicy

    from emd_bench.harness import Window

    t = run.traffic
    index = run.build_index()
    run.mark("index")
    d = run.data
    pool_ids, pool_w = np.asarray(d.q_ids), np.asarray(d.q_w)
    due = schedule(t["rate_qps"], run.seconds, run.seed)
    which = pool_order(len(due) - 1, t["pool"], run.seed)
    policy = ServingPolicy(ladder=("primary",), max_batch=t["max_batch"])
    clock, span = time.monotonic, run.span
    log = LaunchLog(clock, span)

    async def serve():
        async with EmdServer(index, policy, launch_hook=log) as server:
            for b in buckets(t["max_batch"]):    # one launch each
                await asyncio.wait_for(asyncio.gather(*(
                    server.search(pool_ids[i], pool_w[i])
                    for i in range(b))), WARM_UP_S)
            warm = dict(server.stats.bucket_launches)
            server.stats = ServerStats()
            log.clear()

            async def send(k):
                return await server.search(pool_ids[which[k]],
                                           pool_w[which[k]])

            with run.window():
                off = await offer(due, send, clock=clock,
                                  sleep=asyncio.sleep, span=span,
                                  lost_after_s=t["lost_after_s"])
            return off, server.stats, warm

    off, stats, warm = asyncio.run(serve())
    s = summarize(off, log, stats)
    run.note(f"warm-up launches by bucket: {warm}")
    run.note(f"offered {len(off.due)} over {due[-1]:.3f} s; answered "
             f"{int(np.sum(~np.isnan(off.done)))}, lost {off.lost}, "
             f"failed {s['failed']}, shed {stats.shed}")
    if run.trace:
        by_bucket = {}
        for b, ms in zip(log.bucket, log.ms):
            by_bucket.setdefault(b, []).append(ms)
        run.note("launches by bucket (count, median and max host ms): "
                 + ", ".join(f"{b}: {len(v)}, {np.median(v):.3f}, "
                             f"{max(v):.3f}"
                             for b, v in sorted(by_bucket.items())))
    run.note(f"latency ms p50 {s['e2e']['latency_p50_ms']:.3f}, p95 "
             f"{s['e2e']['latency_p95_ms']:.3f}; counters: "
             + ", ".join(f"{k} {v:.6g}" for k, v in s["counters"].items()))
    answers = [(int(which[k]), np.asarray(r.scores), np.asarray(r.indices))
               for k, r in enumerate(off.results)
               if r is not None and not isinstance(r, Exception)]
    calls, at = [], 0
    for size in log.size:
        calls.append(which[at:at + size])
        at += size
    return Window(answers=answers, attempted=len(off.due),
                  failed=s["failed"], lost=off.lost, e2e=s["e2e"],
                  counters=s["counters"], calls=calls)
