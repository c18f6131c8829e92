"""Stable names of the search layers, as the profiler sees them.

Device work carries a ``jax.named_scope`` (compile-time metadata: it
lands in each HLO instruction's ``op_name`` and costs nothing at run
time); host work inside ``EmdIndex.search`` carries a
``jax.profiler.TraceAnnotation`` (a no-op unless a trace is running).
Each scope is opened once, in the function that does the work, so every
engine that passes through that function carries it:

========================  ==================================================
``emd.phase1``            distances, the per-row top-k and ``take_bins``
``emd.phase2``            a Phase-2/3 consumer: query blocks and the pour
``emd.ladder_gather``     the per-slot ladder gathers inside ``emd.phase2``
``emd.topl``              the top-l of a plain search
``emd.cascade.<stage>``   one cascade stage (``stage_rows`` keys:
                          ``stage1.wcd``, ``rescore.act``, ...) with its
                          selection
``emd.search``            host: ``EmdIndex.search``, with the children
                          ``.check``, ``.score`` and ``.topl``
========================  ==================================================
"""
from __future__ import annotations

import functools

import jax

PHASE1 = "emd.phase1"
PHASE2 = "emd.phase2"
LADDER_GATHER = "emd.ladder_gather"
TOPL = "emd.topl"
SEARCH = "emd.search"


def cascade(stage: str) -> str:
    """Scope of one cascade stage, by its ``cascade.search.stage_rows``
    key (``stage2.rwmd``, ``rescore.act``)."""
    return f"emd.cascade.{stage}"


def scoped(name: str):
    """Run the decorated function's trace inside ``jax.named_scope(name)``
    (a fresh context per call: one shared ``named_scope`` object is not
    reentrant)."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco
