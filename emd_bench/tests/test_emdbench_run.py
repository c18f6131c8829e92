"""Whole runs of the benchmark on the CPU at tiny sizes: the command
refuses without a TPU; past that look, a sound program is correct, and a
broken one, or the reference in a lower precision, is not."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import emdbench_tiny as tiny

from emd_bench import check, control

CELLS = ["news-act7-batch", "mnist-act7-batch", "news-fast-cascade",
         "news-serve-poisson"]


def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "emd_bench/run.py", "--workload", "news-act7-batch",
         "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _command(tiny.ROOT)
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "emd_bench", tmp_path / "emd_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _command(tmp_path)
    assert r.returncode != 0 and r.stdout == ""


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    out = tiny.run_tiny(tiny.tiny_cell(name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    json.dumps(out)
    e2e = {m["name"] for m in tiny.load(name).end_to_end}
    assert set(out["metrics"]) == e2e and "setup_s" in e2e


def _alter_one_answer(search):
    def broken(self, q_ids, q_w, *a, **k):
        s, r = search(self, q_ids, q_w, *a, **k)
        return s, r.at[..., 0].set((r[..., 0] + 1) % self.n)
    return broken


def _drop_half_the_batch(search):
    def broken(self, q_ids, q_w, *a, **k):
        s, r = search(self, q_ids, q_w, *a, **k)
        if r.ndim == 1 or r.shape[0] < 2:
            return s, r
        h = r.shape[0] // 2
        rep = np.arange(r.shape[0]) % h         # the first half's answers
        return s[rep], r[rep]
    return broken


FAULTS = [("news-act7-batch", _alter_one_answer),
          ("news-act7-batch", _drop_half_the_batch),
          ("mnist-act7-batch", _alter_one_answer),
          ("mnist-act7-batch", _drop_half_the_batch),
          ("news-fast-cascade", _alter_one_answer),
          ("news-fast-cascade", _drop_half_the_batch),
          ("news-serve-poisson", _alter_one_answer)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}" for n, f in FAULTS])
def test_broken_program_is_not_correct(monkeypatch, name, fault):
    from repro.api import EmdIndex

    monkeypatch.setattr(EmdIndex, "search", fault(EmdIndex.search))
    out = tiny.run_tiny(tiny.tiny_cell(name, check_sample=32))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_controls_are_not_correct(name):
    """The reference in the program's place, a precision below the
    configuration's, fails the cell's limits: ``high`` where float32
    products are not exact in three bfloat16 passes (the text cells),
    ``bf16`` ladders everywhere. On the image grid every coordinate
    splits exactly into two bfloat16 parts, so ``high`` equals
    ``highest`` there and ``bf16`` is the control."""
    cell = tiny.tiny_cell(name)
    r = control.readings(cell, 5, 1.0)
    assert check.verdict(r["program"], cell.limits)[0], r
    assert not check.verdict(r["bf16"], cell.limits)[0], r
    if cell.config["generator"]["name"] == "text":
        assert not check.verdict(r["high"], cell.limits)[0], r
    else:
        assert r["high"]["score_err"] == 0.0 and r["high"]["topl_gap"] == 0.0
