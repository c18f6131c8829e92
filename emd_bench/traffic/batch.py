"""Closed-loop batch search: one caller sends ``queries_per_call``
held-out queries per ``EmdIndex.search`` call and sends the next call
once the top-l of the last has reached the host.

Parameters (``traffic/<mix>.json``): ``pool`` held-out queries, cycled
in calls of ``queries_per_call`` in an order drawn from the seed
(``pool`` is a multiple of ``queries_per_call``); ``engine`` settings
over the configuration's; ``check_sample`` answers compared with the
reference; ``metric``, the name of the end-to-end rate the mix reports
(``queries_per_s`` unless it says otherwise).

The rate is the queries answered in the window over the window's whole
time; the window ends at a call boundary, the first after
``--seconds``. Each call's host-clock time goes to standard error, so a
slow run shows whether every call or one call was slow.
"""
from __future__ import annotations

import time

import jax
import numpy as np


def run(run):
    from emd_bench.harness import Window

    t = run.traffic
    qpc, pool = t["queries_per_call"], t["pool"]
    if pool % qpc:
        raise ValueError(f"pool {pool} is not a multiple of "
                         f"queries_per_call {qpc}")
    index = run.build_index()
    run.mark("index")
    d = run.data
    order = np.random.default_rng(run.seed & (2**63 - 1)).permutation(
        pool // qpc)
    calls = [np.arange(c * qpc, (c + 1) * qpc) for c in order]
    batches = [(d.q_ids[c[0]:c[-1] + 1], d.q_w[c[0]:c[-1] + 1])
               for c in calls]
    jax.block_until_ready(batches)
    jax.block_until_ready(index.search(*batches[0]))     # the one shape
    answers, done, ends = [], [], []
    with run.window():
        t0 = time.monotonic()
        i = 0
        while True:
            c = i % len(calls)
            with run.span("search"):
                s, r = index.search(*batches[c])
            with run.span("fetch"):
                s, r = np.asarray(s), np.asarray(r)
            ends.append(time.monotonic())
            answers.extend(zip(calls[c].tolist(), s, r))
            done.append(calls[c])
            i += 1
            if ends[-1] - t0 >= run.seconds:
                break
        elapsed = ends[-1] - t0
    n = len(answers)
    call_s = np.diff([t0] + ends)
    run.note("calls: " + " ".join(f"{x:.4f}" for x in call_s) + " s")
    return Window(answers=answers, attempted=n, failed=0, lost=0,
                  e2e={t.get("metric", "queries_per_s"): n / elapsed},
                  counters={"queries": n, "calls": len(done)}, calls=done)
