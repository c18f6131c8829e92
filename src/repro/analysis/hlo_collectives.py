"""Collective-traffic extraction from SPMD-partitioned HLO text — with
while-loop trip-count multiplication and a ring wire-byte model.

``compiled.as_text()`` shows per-device result types on each op; operands
are bare references, so sizes are derived from the RESULT type plus the
replica-group size g:

  all-gather       wire = R (g-1) / g x g participants  = R (g-1) x groups
  reduce-scatter   operand O = R g  ->  wire = O (g-1) x groups
  all-reduce       RS + AG            wire = 2 R (g-1) x groups
  all-to-all       wire = R (g-1) x groups
  collective-perm  wire = R x participants

Collectives inside a scanned layer stack live in a while-loop body; XLA
lowers lax.scan to a while whose condition compares the induction variable
to a constant, which we recover and multiply by.

Reduced-precision emulation: the CPU host-mesh oracle cannot run bf16
collectives natively, so XLA widens them — ``convert(bf16 -> f32)`` ->
f32 all-gather -> ``convert`` back — and the textual wire dtype lies
about the program's semantic traffic (a TPU runs the same collective
natively at bf16 width). When a collective's operand is produced by a
convert (or a fusion containing one) from a narrower float into the
collective dtype, bytes are charged at the NARROW width.
"""
from __future__ import annotations

import re
from collections import defaultdict

_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
          "collective-permute")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s+(?P<result>.+?)\s+(?P<kind>all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start|-done)?\(")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_WHILE_RE = re.compile(r"while\(")
_WHILE_ATTR = re.compile(r"(?:condition|body)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_OPERAND_RE = re.compile(
    r"\(\s*(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?\s+)?%([\w\.\-]+)")
#: ``<wide> convert(...%src)`` — the CPU collective-type widener's
#: producer-side upcast into the collective's wire dtype. The operand's
#: own type is printed inline by older XLA (``convert(bf16[..] %src)``)
#: and only at its definition by newer XLA (``convert(%src)``).
_WIDE_CONVERT_RE = re.compile(
    r"=\s*(?P<wide>f32|f64)\[[0-9,]*\](?:\{[^}]*\})?\s+convert\(\s*"
    r"(?:(?P<dtype>[a-z0-9]+)\[[0-9,]*\](?:\{[^}]*\})?\s+)?"
    r"%(?P<src>[\w\.\-]+)")
_NARROW_FLOATS = ("bf16", "f16", "f8e4m3fn", "f8e5m2")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        size = _DTYPE_BYTES[dt]
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size
    return total


def _group_info(line: str, n_devices: int) -> tuple[int, int]:
    """(group size g, num groups)."""
    m = _GROUPS_IOTA.search(line)
    if m:
        return int(m.group(2)), int(m.group(1))
    m = _GROUPS_LIST.search(line)
    if m:
        g = len(m.group(1).split(","))
        return g, max(n_devices // max(g, 1), 1)
    return n_devices, 1


def _wire_bytes(kind: str, result_bytes: int, g: int, groups: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * result_bytes * (g - 1) * groups
    if kind == "reduce-scatter":
        return float(result_bytes * g) * (g - 1) * groups
    if kind == "collective-permute":
        return float(result_bytes) * g * groups
    # all-gather (result already gathered), all-to-all
    return float(result_bytes) * (g - 1) * groups


def _split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    depth = 0
    for line in hlo.splitlines():
        s = line.strip()
        if cur is None:
            if s.endswith("{") and ("->" in s or s.startswith("ENTRY")):
                name = s.split()[0].lstrip("%")
                if name == "ENTRY":
                    name = s.split()[1].lstrip("%")
                comps[name] = []
                cur = name
                depth = 1
            continue
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            cur = None
            continue
        comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    best = 1
    for line in cond_lines:
        for m in _CONST_RE.finditer(line):
            best = max(best, int(m.group(1)))
    return best


def _semantic_scale(line: str, kind: str, comps, comp_lines) -> float:
    """1.0, or narrow/wide itemsize ratio when this collective's operand
    is a widening convert (or a fusion containing one) from a narrower
    float — the CPU oracle's bf16-collective emulation (module docstring).
    """
    rm = _SHAPE_RE.search(line)
    if rm is None or rm.group(1) not in ("f32", "f64"):
        return 1.0          # already narrow (or integer-fenced) wire
    wire = rm.group(1)
    om = _OPERAND_RE.search(line, line.index(kind))
    if not om:
        return 1.0
    opname = om.group(1)
    prod = next((ln for ln in comp_lines
                 if ln.strip().startswith(f"%{opname} ")
                 or f" %{opname} = " in ln), None)
    if prod is None:
        return 1.0
    cands = [prod]
    cm = _CALL_RE.search(prod)
    if cm and "fusion" in prod:
        cands += comps.get(cm.group(1), [])
    for ln in cands:
        cm = _WIDE_CONVERT_RE.search(ln)
        if not cm or cm.group("wide") != wire:
            continue
        narrow = cm.group("dtype") or _defined_dtype(cm.group("src"),
                                                     cands + comp_lines)
        if narrow in _NARROW_FLOATS:
            return _DTYPE_BYTES[narrow] / _DTYPE_BYTES[wire]
    return 1.0


def _defined_dtype(name: str, lines) -> str | None:
    """Element type at the definition ``%name = <dtype>[...`` in
    ``lines``, or None."""
    pat = re.compile(rf"%{re.escape(name)}\s+=\s+([a-z0-9]+)\[")
    for ln in lines:
        m = pat.search(ln)
        if m:
            return m.group(1)
    return None


def collective_bytes(hlo: str, n_devices: int) -> dict[str, float]:
    """Per-kind GLOBAL collective wire bytes, trip-count aware."""
    comps = _split_computations(hlo)

    def comp_cost(name: str, seen: tuple = ()) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        if name not in comps or name in seen:
            return out
        for line in comps[name]:
            s = line.strip()
            if s.startswith("//"):
                continue
            m = _OP_RE.search(s)
            if m:
                kind = m.group("kind")
                rb = _shape_bytes(m.group("result"))
                g, groups = _group_info(s, n_devices)
                scale = _semantic_scale(s, kind, comps, comps[name])
                out[kind] += _wire_bytes(kind, int(rb * scale), g, groups)
                continue
            if _WHILE_RE.search(s):
                cm_cond = re.search(r"condition=%?([\w\.\-]+)", s)
                cm_body = re.search(r"body=%?([\w\.\-]+)", s)
                if cm_cond and cm_body:
                    n = _trip_count(comps.get(cm_cond.group(1), []))
                    for k, v in comp_cost(cm_body.group(1),
                                          seen + (name,)).items():
                        out[k] += v * n
                continue
            cm = _CALL_RE.search(s)
            if cm and "fusion" not in s:
                for k, v in comp_cost(cm.group(1), seen + (name,)).items():
                    out[k] += v
        return out

    entry = None
    mm = re.search(r"ENTRY %?([\w\.\-]+)", hlo)
    if mm:
        entry = mm.group(1)
    elif comps:
        entry = next(iter(comps))
    return dict(comp_cost(entry or ""))
