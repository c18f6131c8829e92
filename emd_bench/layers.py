"""Device time of the program's layers, from the trace and the programs.

The program names its layers with ``jax.named_scope`` (``emd.phase1``,
``emd.phase2``, ``emd.ladder_gather``, ``emd.topl``,
``emd.cascade.<stage>``). A scope is compile-time metadata: it lands in
the ``op_name`` of each HLO instruction of the compiled program. The
trace reduction (``trace.py``) gives the device self time of each
operation by its label (instruction name, kind and shape); this module
gives each label the scope path of its instruction, read from the HLO of
the programs the process holds (the backend's live executables), and
sums the self times per scope path. Nothing here changes how a time is
measured.

An instruction's scope path is the ``emd.`` names in its ``op_name``,
outermost first (``emd.phase2/emd.ladder_gather``). Instructions XLA adds
carry no ``op_name``: a relayout copy, an async copy, a loop that moves
the result of a gather. Such an instruction takes the innermost scope
among its operands' (the first, where two are as deep), followed through
operands that have no ``op_name`` either: a copy is its source's, a loop
that moves a gather's result is the gather's, not the enclosing phase's
whose loop counter it also carries. In the body of a loop, a parameter
takes the scope of the loop. An instruction whose ``op_name`` holds no
``emd.`` name is outside every layer.

Eager operations run as programs of their own and carry no scope: those
of ``MODULE_SCOPES`` take the layer named there. ``EmdIndex.search``
runs its top-l so (``-s``, ``lax.top_k``, ``-neg``).

Nesting is innermost-wins: time under ``emd.phase2/emd.ladder_gather``
is the gather's, not the pour's.
"""
from __future__ import annotations

import re

from emd_bench import trace as tr

_SCOPE = re.compile(r"(?<![\w.])emd\.[\w.]*\w")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?(%?[^\s=]+ = .*)$")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLEE = re.compile(
    r"\b(?:body|condition|true_computation|false_computation|to_apply)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
#: Opcodes whose called computations run as operations of their own.
_CALLERS = ("while", "conditional", "call")

#: Programs of single eager operations, by module name, and their layer.
MODULE_SCOPES = {"jit_top_k": "emd.topl", "jit_negative": "emd.topl"}


def scope_path(op_name: str) -> str:
    """The ``emd.`` names of an ``op_name``, outermost first."""
    return "/".join(_SCOPE.findall(op_name))


def _depth(path: str) -> int:
    return path.count("/") + 1 if path else 0


def _operands(text: str, kind: str) -> list[str]:
    """Names of the operands of an instruction's text."""
    start = text.find(f" {kind}(")
    if start < 0:
        return []
    i = start + len(kind) + 2
    depth, j = 1, i
    while j < len(text) and depth:
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        j += 1
    return re.findall(r"%([\w.\-]+)", text[i:j - 1])


class Module:
    """One program's instructions, with what the scope rules need."""

    def __init__(self, hlo_text: str):
        self.name = ""
        self.ins = {}             # name -> (kind, op_name or None, operands,
        #                                    computation, text)
        self.caller = {}          # computation -> instruction that runs it
        entry = comp = None
        for line in hlo_text.splitlines():
            if line.startswith("HloModule "):
                self.name = line.split()[1].rstrip(",")
                continue
            h = _HEADER.match(line)
            if h and not line.startswith(" "):
                comp = h.group(1)
                entry = comp if line.startswith("ENTRY") else entry
                continue
            m = _LINE.match(line)
            if comp is None or m is None:
                continue
            text = m.group(1)
            e = tr.parse_op(text, 0, 0)
            if not e.kind:
                continue
            on = _OP_NAME.search(text)
            self.ins[e.name] = (e.kind, on.group(1) if on else None,
                                _operands(text, e.kind), comp, text)
            if e.kind in _CALLERS:
                for c in _CALLEE.findall(text):
                    self.caller[c] = e.name
                for group in _BRANCHES.findall(text):
                    for c in re.findall(r"%?([\w.\-]+)", group):
                        self.caller[c] = e.name
        self._memo: dict[str, str | None] = {}
        # Computations that run as operations: the entry and what loops,
        # conditionals and calls run (never the body of a fusion).
        self.runs = {entry}
        grew = True
        while grew:
            more = {c for c, i in self.caller.items()
                    if self.ins[i][3] in self.runs} - self.runs
            self.runs |= more
            grew = bool(more)

    def executed(self):
        """Names of the instructions that run as operations of their
        own."""
        return [n for n, v in self.ins.items() if v[3] in self.runs]

    def scope(self, name: str) -> str:
        """The scope path of instruction ``name`` (see the module doc)."""
        got = self._resolve(name, set())
        return got if got is not None else ""

    def _resolve(self, name: str, seen: set) -> str | None:
        if name in self._memo:
            return self._memo[name]
        if name in seen or name not in self.ins:
            return None
        seen.add(name)
        kind, op_name, operands, comp, _ = self.ins[name]
        if op_name:
            got = scope_path(op_name)
        else:
            found = [s for s in (self._resolve(o, seen) for o in operands)
                     if s is not None]
            got = max(found, key=_depth) if found else None
            if got is None and comp in self.caller:
                got = self._resolve(self.caller[comp], seen)
        self._memo[name] = got
        return got


def label_scopes(hlo_texts) -> tuple[dict[str, str | None], bool]:
    """Scope path of each operation label (``trace.Event.label``) of the
    programs ``hlo_texts``, and whether any instruction carries an
    ``emd.`` scope in its ``op_name``. A label that two programs give
    different paths maps to ``None``."""
    out: dict[str, str | None] = {}
    scoped = False
    for text in hlo_texts:
        mod = Module(text)
        fallback = MODULE_SCOPES.get(mod.name, "")
        for name in mod.executed():
            _, op_name, _, _, ins_text = mod.ins[name]
            scoped = scoped or bool(op_name and _SCOPE.search(op_name))
            path = mod.scope(name) or fallback
            label = tr.parse_op(ins_text, 0, 0).label
            if out.get(label, path) != path:
                path = None
            out[label] = path
    return out, scoped


def live_hlo_texts() -> list[str]:
    """HLO text of every program the process holds on its backend."""
    from jax.extend import backend

    texts = []
    for exe in backend.get_backend().live_executables():
        try:
            texts.extend(m.to_string() for m in exe.hlo_modules())
        except Exception:  # noqa: BLE001
            continue              # a program that will not print is left out
    return texts


def layer_seconds(rec) -> dict[str, float] | None:
    """Device self seconds of the window per scope path (``""`` for time
    in no layer), or ``None`` where the programs carry no ``emd.`` scope
    or there is no trace. Read once per run."""
    if rec.trace is None:
        return None
    cached = getattr(rec.run, "layer_scopes", None)
    if cached is None:
        cached = label_scopes(live_hlo_texts())
        rec.run.layer_scopes = cached
    scopes, scoped = cached
    if not scoped:
        return None
    out: dict[str, float] = {}
    for label, s in rec.trace.op_s.items():
        path = scopes.get(label) or ""
        out[path] = out.get(path, 0.0) + s
    return out


def ms_per_query(rec, keep) -> float | None:
    """Milliseconds per query answered in the window of the device time
    whose scope path ``keep(path)`` accepts; 0.0 where that layer took
    none."""
    per = layer_seconds(rec)
    queries = rec.win.counters.get("queries")
    if per is None or not queries:
        return None
    return 1e3 * sum(s for p, s in per.items() if p and keep(p)) / queries


def in_scope(name: str):
    """A test of scope paths: innermost scope ``name``."""
    return lambda path: path.rsplit("/", 1)[-1] == name


def under(prefix: str):
    """A test of scope paths: some scope of the path starts with
    ``prefix`` (``emd.cascade.stage2.``)."""
    return lambda path: any(p.startswith(prefix) for p in path.split("/"))


def unscoped_pct(rec) -> float | None:
    """Share of the device's busy time spent in no ``emd.`` scope."""
    per = layer_seconds(rec)
    if per is None or rec.trace.busy_s <= 0:
        return None
    return 100.0 * per.get("", 0.0) / rec.trace.busy_s
