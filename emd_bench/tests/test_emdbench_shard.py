"""A corpus whose rows lie over the cell's chips, on four CPU host
devices in a child process: the rows each chip makes are the rows one
device makes, the reference scored chip by chip is the one-device
reference bit for bit, and a tiny distributed cell runs correct. Layouts
that cannot hold are refused, in this process."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

import emdbench_tiny as tiny

from emd_bench import harness

CHIPS = 4
N = 512            # 2 chunks of 64 rows on each of 4 chips

_CHILD = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path[:0] = [sys.argv[1]]
    import numpy as np
    import jax, jax.numpy as jnp
    import emdbench_tiny as tiny
    from emd_bench import harness
    from emd_bench.gen import image, text
    from emd_bench.reference import Reference

    CHIPS, N = int(sys.argv[2]), int(sys.argv[3])
    out = {}

    def sharded_cell(name, **config):
        cell = tiny.tiny_cell(name)
        c = dict(cell.config, n=N, rows_over_chips=True, **config,
                 engine=dict(cell.config["engine"], backend="distributed"))
        return dataclasses.replace(cell, chips=CHIPS, config=c)

    def whole(d):
        return [np.asarray(x) for x in (d.ids, d.w, d.q_ids, d.q_w,
                                        d.coords, d.doc_len)]

    mesh = harness.cell_mesh(CHIPS)
    for name, gen in (("news-act7-batch", text), ("mnist-act7-batch", image)):
        c = sharded_cell(name).config
        seed = 2**33 + 7
        one, over = gen.make(c, seed, 8), gen.make(c, seed, 8, mesh=mesh)
        out[gen.__name__.split(".")[-1]] = {
            "equal": all(np.array_equal(a, b)
                         for a, b in zip(whole(one), whole(over))),
            "blocks": sorted((s.index[0].start, s.data.shape[0], s.device.id)
                             for s in over.ids.addressable_shards),
            "model_order": [d.id for d in mesh.devices[0]],
        }

    cell = sharded_cell("news-act7-batch")
    over = harness.make_data(cell, 11)
    one_ids, one_w = jnp.asarray(np.asarray(over.ids)), \\
        jnp.asarray(np.asarray(over.w))
    engines = {
        "act": dict(method="act", iters=7),
        "rwmd": dict(method="rwmd", iters=0),
        "wcd": dict(method="wcd", iters=0),
        "cascade": dict(stages=[["wcd", 0.4], ["rwmd", 0.05]],
                        rescorer=["act", 3]),
    }
    for key, e in engines.items():
        e = dict(e, top_l=16)
        a = Reference(over.ids, over.w, over.coords, e, 1e-3)
        b = Reference(one_ids, one_w, over.coords, e, 1e-3)
        same = []
        for q in range(4):
            ra = [np.asarray(x) for x in a.query(over.q_ids[q], over.q_w[q])]
            rb = [np.asarray(x) for x in b.query(over.q_ids[q], over.q_w[q])]
            same.append(all(np.array_equal(x, y) for x, y in zip(ra, rb)))
        out["ref_" + key] = {"blocks": len(a.blocks), "same": same}

    res = tiny.run_tiny(cell, seed=5, seconds=1.0)
    out["run"] = {"correct": res["correct"], "checks": res["checks"],
                  "attempted": res["attempted"],
                  "devices": res["device"]["count"]}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}",
               PYTHONPATH=str(tiny.ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", _CHILD,
                        str(tiny.ROOT / "emd_bench" / "tests"), str(CHIPS),
                        str(N)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("gen", ["text", "image"])
def test_rows_made_over_chips_are_the_one_device_rows(child, gen):
    """Bit for bit, and each chip holds one contiguous block of n / chips
    rows, in the order of the mesh's ``model`` axis."""
    got = child[gen]
    assert got["equal"]
    per = N // CHIPS
    assert got["blocks"] == [[i * per, per, dev]
                             for i, dev in enumerate(got["model_order"])]


@pytest.mark.parametrize("engine", ["act", "rwmd", "wcd", "cascade"])
def test_reference_by_chip_is_the_one_device_reference(child, engine):
    """Scores of every row, top-l values and rows, bit for bit."""
    got = child["ref_" + engine]
    assert got["blocks"] == CHIPS and all(got["same"]), got


def test_distributed_cell_with_rows_over_chips_is_correct(child):
    got = child["run"]
    assert got["correct"], got["checks"]
    assert got["devices"] == CHIPS and got["attempted"] > 0


def _sharded(chips=CHIPS, n=N, backend="distributed", **config):
    cell = tiny.tiny_cell("news-act7-batch")
    c = dict(cell.config, n=n, rows_over_chips=True, **config,
             engine=dict(cell.config["engine"], backend=backend))
    return dataclasses.replace(cell, chips=chips, config=c)


def _text_gen():
    return harness.load_module(harness.BENCH / "gen" / "text.py")


def _whole_rows_gen():
    def make(cfg, seed, pool):
        raise AssertionError("never called")
    return types.SimpleNamespace(make=make)


@pytest.mark.parametrize("cell,gen,why", [
    (_sharded(chips=1), _text_gen, "one chip"),
    (_sharded(backend="pallas"), _text_gen, "not distributed"),
    (_sharded(n=N + 64), _text_gen, "not a multiple of chips x chunk"),
    (_sharded(), _whole_rows_gen, "cannot shard"),
], ids=["one-chip", "pallas", "n", "generator"])
def test_layouts_that_cannot_hold_are_refused(cell, gen, why):
    with pytest.raises(harness.Refused, match=why):
        harness.rows_mesh(cell, gen())


def test_configs_without_the_key_keep_whole_rows():
    for name in ("news-act7-batch", "mnist-act7-batch"):
        cell = tiny.load(name)
        assert harness.rows_mesh(cell, None) is None
        assert "rows_over_chips" not in cell.config
