"""Jaxpr hazard detector — walk every step program's closed jaxpr and
flag constructs that would stall or silently bloat the mesh step.

Three hazard classes, found by the single-visit equation walk
``analysis.jaxpr_cost.iter_eqns`` (scan/while/cond/pjit bodies
included):

* **host round-trips** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` (any primitive whose name contains ``callback``),
  infeed/outfeed: each one forces a device->host sync inside what must
  be a single dispatched program. The host-only exact-EMD rescorer is
  exactly the thing this catches if someone traces it into a mesh step.
* **float64 promotions** — each step is traced UNDER x64 mode
  (``jax.enable_x64``) with its real float32/int32 input
  avals; any equation then producing f64/c128 reveals a latent promotion
  (a Python float folded at trace time, an np.float64 constant) that
  doubles memory and collective bytes the moment a caller enables x64.
  All current engines trace clean, so any flag is a regression.
* **oversized captured constants** — closed-over arrays above
  ``max_const_bytes`` (default 1 MiB) get baked into the program and
  replicated to every device instead of arriving as sharded operands.

Pure tracing — no devices, no mesh, no compilation — so this pass runs
in milliseconds per step and needs no ``XLA_FLAGS``.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.analysis.jaxpr_cost import iter_eqns
from repro.analysis.violations import Violation

#: Primitives that force a host round-trip inside a jitted step even
#: though their names do not contain "callback".
_HOST_SYNC_PRIMS = frozenset({"infeed", "outfeed"})

#: dtypes whose appearance under an x64 trace marks a promotion hazard.
#: (int64 is excluded: x64 mode makes every Python-int literal an s64
#: weak type, which is benign and would flag every program.)
_WIDE_FLOATS = frozenset({"float64", "complex128"})

DEFAULT_MAX_CONST_BYTES = 1 << 20


def _is_host_callback(prim_name: str) -> bool:
    return "callback" in prim_name or prim_name in _HOST_SYNC_PRIMS


def check_jaxpr(name: str, closed, *,
                max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
                ) -> list[Violation]:
    """Hazard-scan one already-traced ClosedJaxpr."""
    out: list[Violation] = []
    callbacks: set[str] = set()
    wide: set[str] = set()
    for eqn in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        if _is_host_callback(pname):
            callbacks.add(pname)
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(getattr(aval, "dtype", None), "name", None)
            if dt in _WIDE_FLOATS:
                wide.add(f"{pname}->{dt}")
    for pname in sorted(callbacks):
        out.append(Violation(
            "hazards", name,
            f"host callback primitive {pname!r} inside a jitted step "
            "(forces a device->host sync per dispatch)"))
    for tag in sorted(wide):
        out.append(Violation(
            "hazards", name,
            f"wide-float promotion under x64 tracing: {tag} (a trace-time "
            "constant or np scalar is not pinned to float32)"))
    for i, const in enumerate(getattr(closed, "consts", ()) or ()):
        try:
            nbytes = int(np.asarray(const).nbytes)
        except Exception:  # noqa: BLE001 - opaque closures (fn refs etc.)
            continue
        if nbytes > max_const_bytes:
            out.append(Violation(
                "hazards", name,
                f"captured constant #{i} is {nbytes} bytes "
                f"(> {max_const_bytes}): it will be baked into the "
                "program and replicated to every device rather than "
                "arriving as a sharded operand"))
    return out


def check_fn(name: str, fn, specs, *,
             max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
             ) -> list[Violation]:
    """Trace ``fn`` on ``specs`` under x64 mode and hazard-scan it.

    The input avals keep their declared f32/i32 dtypes — x64 mode only
    changes how TRACE-TIME literals promote, which is exactly the latent
    hazard being probed.
    """
    try:
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(fn)(*specs)
    except Exception as e:  # noqa: BLE001 - surface, don't crash the suite
        return [Violation("hazards", name,
                          f"step failed to trace under x64 mode: {e}")]
    return check_jaxpr(name, closed, max_const_bytes=max_const_bytes)


def run(*, workload=None, pad_multiple: int = 8,
        max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
        extra_fns: dict | None = None) -> tuple[list[Violation], int]:
    """Hazard-scan every registry step case (plus ``extra_fns``, a
    {name: callable} dict traced on the same input specs — the
    seeded-violation tests inject through it)."""
    from repro.analysis.collectives_check import check_workload
    from repro.launch import search as S

    workload = check_workload() if workload is None else workload
    base_specs = S.search_input_specs(workload, pad_multiple=pad_multiple)
    out: list[Violation] = []
    checked = 0
    for case in S.step_cases():
        fn = S.build_step(case, workload)
        # Per-case specs: sourced cascades append their candidate-index
        # state operands (which ALSO puts the big-constant scan on that
        # state — it must arrive as an argument, never baked in).
        specs = S.case_input_specs(case, workload,
                                   pad_multiple=pad_multiple)
        out += check_fn(case.name, fn, specs,
                        max_const_bytes=max_const_bytes)
        checked += 1
    for name, fn in (extra_fns or {}).items():
        out += check_fn(name, fn, base_specs,
                        max_const_bytes=max_const_bytes)
        checked += 1
    return out, checked
