"""Exact jaxpr-level FLOP/byte accounting.

XLA's ``compiled.cost_analysis()`` counts every while/scan body ONCE
(trip counts are invisible to HloCostAnalysis), which under-reports any
scanned-layer model by ~the layer count. This counter walks the closed
jaxpr instead, multiplying scan bodies by their static length, so the
roofline terms the dry-run records (``launch/dryrun.py`` ->
``results/dryrun.jsonl``) are exact for the matmul-dominated workloads
this framework runs.

FLOPs: 2*M*N*K per dot_general (batched dims included), conv as implicit
dot. Bytes: a structural HBM-traffic model — operands+outputs of
dot/conv (weights and activations stream through VMEM once under perfect
fusion), gather/scatter, and big reduction operands. Pure element-wise ops
are assumed fused (not counted); the number is therefore a lower-ish bound
on real traffic and is labelled as such wherever reported.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from jax.extend import core as jcore

_RECURSE_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr")
#: Body-carrying params of the control-flow primitives ``_count`` handles
#: explicitly (with trip-count multiplication); ``iter_eqns`` descends into
#: these too so generic walkers see EVERY equation.
_BODY_PARAM_KEYS = ("body_jaxpr",)


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:  # noqa: BLE001 - abstract tokens etc.
        return 0


def _io_bytes(eqn) -> int:
    n = 0
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            n += _aval_bytes(aval)
    return n


def _dot_flops(eqn) -> int:
    ((lc, _rc), (lb, _rb)) = eqn.params["dimension_numbers"]
    a = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    contract = math.prod(a.shape[i] for i in lc) if lc else 1
    return 2 * int(np.prod(out.shape)) * int(contract)


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    groups = eqn.params.get("feature_group_count", 1)
    # rhs: spatial... x in_feat/groups x out_feat (depends on dim numbers);
    # per output element: 2 * prod(rhs.shape) / out_feat.
    dn = eqn.params["dimension_numbers"]
    out_feat = rhs.shape[dn.rhs_spec[0]]
    per_out = 2 * int(np.prod(rhs.shape)) // max(out_feat, 1)
    del groups
    return int(np.prod(out.shape)) * per_out


def _sub_jaxprs(eqn):
    for key in _RECURSE_PARAM_KEYS:
        if key in eqn.params:
            j = eqn.params[key]
            yield j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j
    if "branches" in eqn.params:                      # cond
        for b in eqn.params["branches"]:
            yield b.jaxpr if isinstance(b, jcore.ClosedJaxpr) else b


def iter_eqns(jaxpr):
    """Yield every equation of ``jaxpr`` and all nested sub-jaxprs
    (scan/while/cond/pjit/remat/pallas_call bodies), each visited once.

    The generic single-visit walk for structural analyses
    (``analysis/hazards`` builds on it); unlike :func:`_count` it applies
    no trip-count weighting — an equation inside a scanned body is
    yielded once however many times the loop runs.
    """
    for eqn in jaxpr.eqns:
        yield eqn
        subs = list(_sub_jaxprs(eqn))
        for key in _BODY_PARAM_KEYS:
            if key in eqn.params:
                j = eqn.params[key]
                subs.append(j.jaxpr if isinstance(j, jcore.ClosedJaxpr)
                            else j)
        for sub in subs:
            yield from iter_eqns(sub)


def _count(jaxpr) -> dict[str, float]:
    flops = 0.0
    nbytes = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            flops += _dot_flops(eqn)
            nbytes += _io_bytes(eqn)
        elif name == "conv_general_dilated":
            flops += _conv_flops(eqn)
            nbytes += _io_bytes(eqn)
        elif name in ("gather", "scatter", "scatter-add", "scatter_add",
                      "dynamic_slice", "dynamic_update_slice"):
            nbytes += _io_bytes(eqn)
        elif name in ("reduce_sum", "reduce_max", "reduce_min", "argmax",
                      "argmin", "reduce_and", "reduce_or", "sort", "top_k",
                      "cumsum", "reduce_prod"):
            nbytes += _io_bytes(eqn)
        elif name == "scan":
            inner = _count(eqn.params["jaxpr"].jaxpr)
            n = eqn.params["length"]
            flops += n * inner["flops"]
            nbytes += n * inner["bytes"]
            continue
        elif name == "while":
            inner = _count(eqn.params["body_jaxpr"].jaxpr)
            flops += inner["flops"]                   # trip count unknown
            nbytes += inner["bytes"]
            continue
        elif name == "cond":
            subs = [_count(b.jaxpr if isinstance(b, jcore.ClosedJaxpr) else b)
                    for b in eqn.params["branches"]]
            flops += max(s["flops"] for s in subs)
            nbytes += max(s["bytes"] for s in subs)
            continue
        elif name == "shard_map":
            # Body avals are PER-SHARD; every device runs the body once, so
            # global cost = body cost x mesh size.
            body = eqn.params.get("jaxpr")
            mesh = eqn.params.get("mesh")
            n_shards = 1
            if mesh is not None:
                try:
                    n_shards = int(np.prod(list(dict(mesh.shape).values())))
                except Exception:  # noqa: BLE001
                    n_shards = 1
            inner = _count(body.jaxpr if isinstance(body, jcore.ClosedJaxpr)
                           else body)
            flops += n_shards * inner["flops"]
            nbytes += n_shards * inner["bytes"]
            continue
        # generic recursion (pjit, remat/checkpoint, custom_vjp, ...)
        for sub in _sub_jaxprs(eqn):
            inner = _count(sub)
            flops += inner["flops"]
            nbytes += inner["bytes"]
    return {"flops": flops, "bytes": nbytes}


def cost_of(fn, *abstract_args, **kw) -> dict[str, float]:
    """Global (unpartitioned) FLOPs and structural HBM bytes of ``fn``."""
    closed = jax.make_jaxpr(fn, **kw)(*abstract_args)
    return _count(closed.jaxpr)
