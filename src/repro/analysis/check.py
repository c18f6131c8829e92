"""Static contract checker — one CLI over every pre-run invariant.

    PYTHONPATH=src python -m repro.analysis.check [--passes ...]

Five default passes (plus the opt-in bench-artifact pass), each a module
in this package returning :class:`~repro.analysis.violations.Violation`
records; the CLI renders a per-pass report and exits non-zero if any
violation survives:

* ``registry``    — bound-table partial order, MethodSpec coherence,
                    cascade-preset admissibility claims
                    (``registry_lint``). Pure Python.
* ``hazards``     — host callbacks / f64 promotions / oversized baked
                    constants in every registry step's jaxpr
                    (``hazards``). Tracing only, no devices.
* ``precision``   — bf16-policy step cases whose Phase-1 handoffs
                    silently stayed float32, or whose precision kwarg
                    was dropped entirely (``precision_lint``). Tracing
                    only, no devices.
* ``vmem``        — Pallas per-core VMEM footprints from the kernels'
                    static block layouts (``vmem``). Pure arithmetic.
* ``collectives`` — partitioned-HLO collective bytes of every step on
                    the 8-device host mesh vs the golden manifest, plus
                    the corpus-scaling all-gather guard
                    (``collectives_check``). Needs the forced host
                    devices — this module sets ``XLA_FLAGS`` itself,
                    which is why its imports stay stdlib-only until
                    after argument parsing.
* ``bench``       — BENCH_*.json artifact sanity (``bench_check``);
                    opt-in (``--passes bench``) since the artifacts only
                    exist after a benchmark run.

``--update-manifests`` regenerates the collective manifest in place
(then still verifies against it — committing the diff is the review).
"""
from __future__ import annotations

import argparse
import os
import sys

#: Pass name -> (module name, included by default).
PASSES = {
    "registry": ("repro.analysis.registry_lint", True),
    "hazards": ("repro.analysis.hazards", True),
    "precision": ("repro.analysis.precision_lint", True),
    "vmem": ("repro.analysis.vmem", True),
    "collectives": ("repro.analysis.collectives_check", True),
    "bench": ("repro.analysis.bench_check", False),
}

_FORCED_DEVICES = 8


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="repro.analysis.check",
        description="static sharding/collective/VMEM/admissibility checks")
    p.add_argument("--passes", default=None,
                   help="comma-separated subset of "
                        f"{','.join(PASSES)} or 'all' "
                        "(default: every pass except bench)")
    p.add_argument("--update-manifests", action="store_true",
                   help="regenerate the golden collective manifest "
                        "before checking against it")
    p.add_argument("--vmem-budget-mb", type=float, default=None,
                   help="per-core VMEM budget the kernel layouts must "
                        "clear (default: the kernels' scoped-VMEM "
                        "request, kernels/tiling.VMEM_LIMIT_BYTES)")
    return p.parse_args(argv)


def _selected(arg: str | None) -> list[str]:
    if arg is None:
        return [n for n, (_, default) in PASSES.items() if default]
    if arg.strip() == "all":
        return list(PASSES)
    names = [s.strip() for s in arg.split(",") if s.strip()]
    bad = [n for n in names if n not in PASSES]
    if bad:
        raise SystemExit(f"unknown pass(es) {bad}; one of {list(PASSES)}")
    return names


def main(argv=None) -> int:
    args = _parse(sys.argv[1:] if argv is None else argv)
    selected = _selected(args.passes)

    if "collectives" in selected and "XLA_FLAGS" not in os.environ:
        # Must happen before anything imports jax: the collective pass
        # compiles on an 8-device host mesh.
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={_FORCED_DEVICES}")

    import importlib

    from repro.analysis.violations import render

    failures = 0
    for name in selected:
        mod = importlib.import_module(PASSES[name][0])
        kwargs = {}
        if name == "vmem" and args.vmem_budget_mb is not None:
            kwargs["budget_bytes"] = int(args.vmem_budget_mb * 2**20)
        if name == "collectives":
            kwargs["update_manifests"] = args.update_manifests
        violations, checked = mod.run(**kwargs)
        print(render(violations, checked=checked, passname=name))
        failures += len(violations)

    print(f"\n{'FAIL' if failures else 'OK'}: {len(selected)} pass(es), "
          f"{failures} violation(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
