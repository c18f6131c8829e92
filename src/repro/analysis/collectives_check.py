"""Collective-contract checker: compile every registry step on the
8-device host mesh and hold its collective traffic to a manifest.

For each :func:`repro.launch.search.step_cases` entry the pass lowers the
jitted step with the real input shardings, compiles it, and extracts
per-kind collective wire bytes from the partitioned HLO
(``analysis.hlo_collectives.collective_bytes`` — trip-count aware, ring
wire model). Two contracts:

* **manifest pin** — the byte profile must equal the checked-in golden
  manifest (``manifests/collectives.json``) exactly. Any partitioner
  regression — a stage constraint dropped, XLA hoisting a reshard above
  the shard-local top-k — shows up as a byte diff long before a profile
  run would catch it. The manifest records the jax version that produced
  it; on a different jax the pin degrades to a warning (partitioner
  output legitimately changes across releases) while the scaling guard
  below still runs. ``--update-manifests`` regenerates.
* **scaling guard** — every case with ``scale_guarded=True`` (the dist
  scores pipelines and the absolute-budget ``cascade:pinned`` ladder) is
  compiled again at double the corpus rows; its all-gather bytes must
  not grow. This machine-checks the PR-4 guarantee that the (nq, n)
  score matrix never crosses the mesh: a corpus-scaled all-gather is
  exactly what a broken ``emd_ladder`` constraint produces. Plain
  ``search`` is exempt (``lax.top_k`` does not partition — its top-l
  legitimately gathers scores; the cascade step exists to avoid that),
  as are fractional-budget cascades (candidate counts scale by design).
* **narrowing** — every bf16 case must all-gather fewer bytes than its
  float32 twin (the same name without ``:bf16``): the policy exists to
  narrow the ladder handoff on the wire.

Requires 8 host devices: the CLI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
initializes.
"""
from __future__ import annotations

import json
import os

import jax

from repro.analysis.hlo_collectives import collective_bytes
from repro.analysis.violations import Violation
from repro.configs.emd_20news import EMDWorkload
from repro.launch import search as S
from repro.launch.mesh import make_test_mesh

#: Mesh the contract is pinned on: 2 data x 4 model host devices.
N_DATA, N_MODEL = 2, 4
N_DEVICES = N_DATA * N_MODEL

#: The tiny tracing workload (compiles in ~1 s/step on the host mesh)
#: and its row padding. Dims are multiples of the mesh axes.
CHECK_PAD_MULTIPLE = 8
_BASE = dict(vocab=96, dim=8, hmax=16, iters=2, queries=16)

#: Corpus rows for the manifest compile and the scaling probe. The probe
#: pair starts at 128, not the manifest's 64: the pinned cascade's
#: shard-local ladder is ``blocks * min(budget, n/blocks)`` wide, so its
#: traffic legitimately grows until every shard holds at least the stage
#: budget (n >= blocks * max_budget = 96 here) and is exactly flat after.
CHECK_N_DB = 64
SCALE_N_DBS = (128, 256)

#: All-gather growth tolerated between the two probe sizes before a
#: guarded case fails (absolute bytes; legitimate steps grow by exactly
#: zero — the slack only absorbs control-flow bookkeeping).
GROWTH_TOLERANCE_BYTES = 2048

MANIFEST_PATH = os.path.join(os.path.dirname(__file__), "manifests",
                             "collectives.json")


def check_workload(n_db: int = CHECK_N_DB) -> EMDWorkload:
    return EMDWorkload(name="chk", n_db=n_db, **_BASE)


def step_collectives(case: S.StepCase, workload, mesh, *,
                     step_fn=None) -> dict[str, float]:
    """Compile one case on ``mesh`` and return its per-kind collective
    wire bytes. ``step_fn`` overrides the registry-built jitted step —
    the seeded-violation tests inject through it. Input specs are
    per-case (``S.case_input_specs``): sourced cascades take their
    candidate-index state as trailing operands."""
    specs = S.case_input_specs(case, workload,
                               pad_multiple=CHECK_PAD_MULTIPLE)
    fn = S.build_step(case, workload, mesh,
                      pad_multiple=CHECK_PAD_MULTIPLE) \
        if step_fn is None else step_fn
    hlo = fn.lower(*specs).compile().as_text()
    return {k: float(v)
            for k, v in sorted(collective_bytes(hlo, N_DEVICES).items())}


def make_mesh():
    if len(jax.devices()) < N_DEVICES:
        raise SystemExit(
            f"the collective checker needs {N_DEVICES} host devices; run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{N_DEVICES} (the repro.analysis.check CLI sets this itself "
            "when it starts before jax does)")
    return make_test_mesh(N_DATA, N_MODEL)


def load_manifest(path: str = MANIFEST_PATH) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def build_manifest(mesh=None) -> dict:
    """Compile every case and record its byte profile."""
    mesh = make_mesh() if mesh is None else mesh
    w = check_workload()
    steps = {c.name: step_collectives(c, w, mesh) for c in S.step_cases()}
    return {
        "jax": jax.__version__,
        "n_devices": N_DEVICES,
        "mesh": [N_DATA, N_MODEL],
        "workload": dict(n_db=CHECK_N_DB, **_BASE),
        "pad_multiple": CHECK_PAD_MULTIPLE,
        "steps": steps,
    }


def write_manifest(manifest: dict, path: str = MANIFEST_PATH) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.write("\n")


def check_scaling(case: S.StepCase, mesh, *,
                  small_fn=None, big_fn=None) -> list[Violation]:
    """All-gather bytes must not grow with the corpus for a guarded case.

    ``small_fn``/``big_fn`` override the two jitted steps (seeded tests).
    """
    n0, n1 = SCALE_N_DBS
    small = step_collectives(case, check_workload(n0), mesh,
                             step_fn=small_fn)
    big = step_collectives(case, check_workload(n1), mesh,
                           step_fn=big_fn)
    ag0 = small.get("all-gather", 0.0)
    ag1 = big.get("all-gather", 0.0)
    if ag1 > ag0 + GROWTH_TOLERANCE_BYTES:
        return [Violation(
            "collectives", case.name,
            f"all-gather bytes scale with the corpus: {ag0:.0f} at "
            f"n={n0} -> {ag1:.0f} at n={n1} — an array "
            "sized by the database rows is crossing the mesh (the "
            "shard-local top-budget / emd_ladder contract is broken)")]
    return []


def check_narrowing(steps: dict[str, dict], cases) -> list[Violation]:
    """bf16 cases whose all-gather bytes (``steps``: name -> profile) do
    not undercut their float32 twin's."""
    out = []
    for case in cases:
        if case.precision != "bf16" or case.name not in steps:
            continue
        twin = case.name.removesuffix(":bf16")
        narrow = steps[case.name].get("all-gather", 0.0)
        wide = steps.get(twin, {}).get("all-gather", 0.0)
        if not narrow < wide:
            out.append(Violation(
                "collectives", case.name,
                f"bf16 step all-gathers {narrow:.0f} bytes, not fewer than "
                f"its float32 twin {twin} ({wide:.0f}) — the narrowed "
                "handoff is crossing the mesh at full width"))
    return out


def run(*, update_manifests: bool = False,
        manifest_path: str = MANIFEST_PATH,
        ) -> tuple[list[Violation], int]:
    """Manifest pin + scaling guard over every registry case."""
    mesh = make_mesh()
    out: list[Violation] = []
    cases = S.step_cases()

    if update_manifests:
        write_manifest(build_manifest(mesh), manifest_path)

    manifest = load_manifest(manifest_path)
    if manifest is None:
        out.append(Violation(
            "collectives", "manifest",
            f"no golden manifest at {manifest_path}; run the CLI with "
            "--update-manifests and commit the result"))
        pinned = {}
        pin_enforced = False
    else:
        pinned = manifest.get("steps", {})
        pin_enforced = manifest.get("jax") == jax.__version__
        if not pin_enforced:
            print(f"collectives: manifest was built on jax "
                  f"{manifest.get('jax')!r}, running {jax.__version__} — "
                  "byte pins reported as warnings only; scaling guard "
                  "still enforced")

    w = check_workload()
    measured = {}
    for case in cases:
        got = measured[case.name] = step_collectives(case, w, mesh)
        want = pinned.get(case.name)
        if want is None:
            if manifest is not None:
                out.append(Violation(
                    "collectives", case.name,
                    "step missing from the golden manifest — rerun with "
                    "--update-manifests and review the new profile"))
        elif got != want:
            msg = (f"collective profile drifted from the manifest: "
                   f"got {got}, pinned {want}")
            if pin_enforced:
                out.append(Violation("collectives", case.name, msg))
            else:
                print(f"collectives: WARN {case.name}: {msg}")
        if case.scale_guarded:
            out += check_scaling(case, mesh)
    out += check_narrowing(measured, cases)

    stale = sorted(set(pinned) - {c.name for c in cases})
    for name in stale:
        out.append(Violation(
            "collectives", name,
            "manifest pins a step the registry no longer enumerates — "
            "rerun with --update-manifests"))
    return out, len(cases)
