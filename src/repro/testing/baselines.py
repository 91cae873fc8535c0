"""Golden-baseline store: recorded zoo expectations + replayable artifacts.

A *baseline* is the committed, human-reviewable expectation for one zoo
case: did the debugger detect waste, on which side, with which root-cause
class, at which (analytic, deterministic) energies — plus a declared
tolerance for the energy fields.  Baselines live as one JSON file per case
under ``tests/baselines/``; the golden *artifacts* backing them live in a
content-addressed :class:`~repro.core.artifact.ArtifactStore` under
``tests/baselines/store`` (not committed — regenerable by ``record``).

Two replay modes:

* ``check`` (live) — re-captures the case through the session; with a warm
  store this is a pure cache hit, with a cold one it re-runs the pipeline.
  Either way the fresh comparison is diffed against the committed JSON.
* ``check --offline`` — loads the golden artifacts from the store and
  re-runs matching + classification + diagnosis with **zero instrumented
  execution** (the record-time compare memoized every phase-2 tensor value
  it fetched onto the artifacts; a replay that needs values beyond that
  set has, by definition, changed matcher behavior and is reported as
  drift).  This is the CI drift gate: a matcher or diagnosis regression
  changes the replayed findings even though no candidate code ran.

Drift is reported field-by-field as :class:`Drift` records, never as a bare
boolean, so a CI failure names exactly what moved.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from pathlib import Path
from typing import Any, Mapping, Sequence

from repro.core.artifact import ArtifactStore, ArtifactValueError
from repro.core.diagnose import DIAGNOSIS_KINDS
from repro.core.report import Report
from repro.core.session import Session
from repro.core.store import StoreError
from repro.zoo.cases import Case

BASELINE_FORMAT_VERSION = 1
DEFAULT_BASELINE_DIR = "tests/baselines"
# Offline replay is deterministic (same artifacts, same matcher, same
# pricing), so the default declared tolerance is tight; recorders can widen
# it per-case for energies that depend on measured time (replay backend).
DEFAULT_ENERGY_RTOL = 1e-6


class BaselineError(RuntimeError):
    """A baseline could not be recorded or replayed."""


class MissingBaselineError(BaselineError, KeyError):
    """No recorded baseline for the requested case."""


@dataclasses.dataclass
class WasteExpectation:
    """The committed signature of one energy-waste finding."""

    wasteful_side: str           # 'A' (inefficient twin) | 'B'
    kind: str | None             # diagnosis root-cause class
    energy_a_j: float
    energy_b_j: float
    nodes_a: int                 # region sizes, not node ids: stable under
    nodes_b: int                 # graph-identical re-traces

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "WasteExpectation":
        return cls(wasteful_side=d["wasteful_side"], kind=d["kind"],
                   energy_a_j=d["energy_a_j"], energy_b_j=d["energy_b_j"],
                   nodes_a=d["nodes_a"], nodes_b=d["nodes_b"])


@dataclasses.dataclass
class Baseline:
    """Committed expectation for one zoo case."""

    case_id: str
    paper_id: str
    category: str
    expect_detect: bool
    backend_id: str
    sample_seeds: list[int]
    detected: bool
    total_energy_a_j: float
    total_energy_b_j: float
    regions: int
    eq_tensor_pairs: int         # matcher-quality canary
    waste: list[WasteExpectation]
    tradeoffs: int
    comparable: int
    energy_rtol: float = DEFAULT_ENERGY_RTOL

    @classmethod
    def from_report(cls, case: Case, report: Report, *, backend_id: str,
                    sample_seeds: Sequence[int],
                    energy_rtol: float = DEFAULT_ENERGY_RTOL) -> "Baseline":
        waste = [WasteExpectation(
            wasteful_side=f.wasteful_side,
            kind=f.diagnosis.kind if f.diagnosis else None,
            energy_a_j=f.energy_a_j, energy_b_j=f.energy_b_j,
            nodes_a=len(f.nodes_a), nodes_b=len(f.nodes_b))
            for f in report.waste_findings]
        for w in waste:
            if w.kind is not None and w.kind not in DIAGNOSIS_KINDS:
                raise BaselineError(f"{case.id}: unknown diagnosis kind "
                                    f"{w.kind!r} (not in {DIAGNOSIS_KINDS})")
        by_cls = {"tradeoff": 0, "comparable": 0}
        for f in report.findings:
            if f.classification in by_cls:
                by_cls[f.classification] += 1
        return cls(case_id=case.id, paper_id=case.paper_id,
                   category=case.category, expect_detect=case.expect_detect,
                   backend_id=backend_id,
                   sample_seeds=[int(s) for s in sample_seeds],
                   detected=bool(waste),
                   total_energy_a_j=report.total_energy_a_j,
                   total_energy_b_j=report.total_energy_b_j,
                   regions=len(report.findings),
                   eq_tensor_pairs=int(report.meta.get("eq_tensor_pairs", 0)),
                   waste=waste, tradeoffs=by_cls["tradeoff"],
                   comparable=by_cls["comparable"], energy_rtol=energy_rtol)

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["format_version"] = BASELINE_FORMAT_VERSION
        return json.dumps(d, indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, data: str | Mapping[str, Any]) -> "Baseline":
        d = json.loads(data) if isinstance(data, str) else dict(data)
        version = d.pop("format_version", BASELINE_FORMAT_VERSION)
        if version != BASELINE_FORMAT_VERSION:
            raise BaselineError(f"baseline format v{version}; this build "
                                f"reads v{BASELINE_FORMAT_VERSION}")
        d["waste"] = [WasteExpectation.from_dict(w) for w in d["waste"]]
        return cls(**d)


@dataclasses.dataclass
class Drift:
    """One divergence between a committed baseline and a fresh replay."""

    case_id: str
    field: str
    expected: Any
    actual: Any

    def __str__(self) -> str:
        return (f"{self.case_id}: {self.field} drifted — "
                f"expected {self.expected!r}, got {self.actual!r}")


def rel_diff(a: float, b: float) -> float:
    """Symmetric relative difference, safe at zero — the drift metric the
    baseline gate and the serving-audit drift check (repro.audit) share."""
    scale = max(abs(a), abs(b), 1e-30)
    return abs(a - b) / scale


_rel_diff = rel_diff                           # historical private alias


def diff_baselines(expected: Baseline, actual: Baseline) -> list[Drift]:
    """Field-by-field drift between a committed baseline and a fresh one.

    Structural fields (detection verdict, waste sign, root-cause class,
    finding/region counts, matched-pair count) compare exactly; energy
    fields compare within the baseline's declared ``energy_rtol``.
    """
    cid = expected.case_id
    out: list[Drift] = []

    def exact(field: str, e, a) -> None:
        if e != a:
            out.append(Drift(cid, field, e, a))

    def energy(field: str, e: float, a: float) -> None:
        if _rel_diff(e, a) > expected.energy_rtol:
            out.append(Drift(cid, field, e, a))

    exact("backend_id", expected.backend_id, actual.backend_id)
    exact("sample_seeds", expected.sample_seeds, actual.sample_seeds)
    exact("detected", expected.detected, actual.detected)
    exact("regions", expected.regions, actual.regions)
    exact("eq_tensor_pairs", expected.eq_tensor_pairs, actual.eq_tensor_pairs)
    exact("waste_findings", len(expected.waste), len(actual.waste))
    exact("tradeoffs", expected.tradeoffs, actual.tradeoffs)
    exact("comparable", expected.comparable, actual.comparable)
    energy("total_energy_a_j", expected.total_energy_a_j,
           actual.total_energy_a_j)
    energy("total_energy_b_j", expected.total_energy_b_j,
           actual.total_energy_b_j)
    for i, (we, wa) in enumerate(zip(expected.waste, actual.waste)):
        exact(f"waste[{i}].wasteful_side", we.wasteful_side, wa.wasteful_side)
        exact(f"waste[{i}].kind", we.kind, wa.kind)
        exact(f"waste[{i}].nodes_a", we.nodes_a, wa.nodes_a)
        exact(f"waste[{i}].nodes_b", we.nodes_b, wa.nodes_b)
        energy(f"waste[{i}].energy_a_j", we.energy_a_j, wa.energy_a_j)
        energy(f"waste[{i}].energy_b_j", we.energy_b_j, wa.energy_b_j)
    return out


@dataclasses.dataclass
class RecordResult:
    baseline: Baseline
    report: Report
    art_a: Any                   # CandidateArtifact (live)
    art_b: Any


class BaselineStore:
    """``<root>/<case-id>.json`` expectations + ``<root>/store`` artifacts.

    The session's artifact store is forced to the baseline artifact store so
    record-time captures/compares persist (and memoize phase-2 evidence
    into) the golden artifacts that ``check --offline`` replays.

    ``artifact_store`` overrides the default ``<root>/store`` location with
    any store URI — e.g. a ``file://`` NFS mirror a fleet shares, or an
    ``http://`` readonly mirror for pure offline checks.

    By default the golden store is **sketch-only** (``sketch_only=True``):
    record persists phase-1 streamed signatures, phase-2 value digests and
    unfolding spectra, but no raw value chunks — offline replay decides
    every recorded match from the manifest alone (zero raw-value chunk
    reads), which is what keeps the committed-zoo store small.  Pass
    ``sketch_only=False`` to keep raw values (needed only if the store must
    also serve *new* comparisons offline, beyond drift replay).
    """

    def __init__(self, root: str | Path = DEFAULT_BASELINE_DIR, *,
                 session: Session | None = None,
                 artifact_store: "ArtifactStore | str | None" = None,
                 sketch_only: bool = True):
        self.root = Path(root)
        if artifact_store is None:
            self.artifacts = ArtifactStore(self.root / "store")
        else:
            self.artifacts = ArtifactStore.from_uri(artifact_store)
        self.artifacts.persist_raw_values = not sketch_only
        self.session = session or Session()
        self.session.store = self.artifacts
        # Baselines are the fidelity reference: a degraded capture or
        # sketch-only-degraded compare must never be silently recorded as
        # (or diffed against) golden truth.  Failures surface as typed
        # errors / Drift records instead of riding the degradation ladder.
        self.session.allow_degraded = False

    # -- paths / committed JSON --------------------------------------------
    def baseline_path(self, case_id: str) -> Path:
        return self.root / f"{case_id}.json"

    @property
    def index_path(self) -> Path:
        """case-id -> golden artifact keys.  Lives next to the committed
        JSON expectations (NOT inside the artifact store), so an offline
        check can point ``artifact_store`` at a shared readonly mirror that
        only carries manifests + chunks."""
        return self.root / "index.json"

    def recorded_ids(self) -> list[str]:
        if not self.root.exists():
            return []
        # index.json (case-id -> artifact keys) lives next to the per-case
        # expectations and is not a baseline itself
        return sorted(p.stem for p in self.root.glob("*.json")
                      if p.name != "index.json")

    def load(self, case_id: str) -> Baseline:
        path = self.baseline_path(case_id)
        if not path.exists():
            raise MissingBaselineError(
                f"no baseline for {case_id!r} under {self.root} — run "
                f"`python -m repro.cli baseline record {case_id}` first")
        return Baseline.from_json(path.read_text())

    def _write_json(self, path: Path, text: str) -> None:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".json.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(text)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    def _load_index(self) -> dict[str, dict[str, str]]:
        if not self.index_path.exists():
            return {}
        return json.loads(self.index_path.read_text())

    def _update_index(self, case_id: str, key_a: str, key_b: str) -> None:
        idx = self._load_index()
        idx[case_id] = {"a": key_a, "b": key_b}
        self._write_json(self.index_path,
                         json.dumps(idx, indent=2, sort_keys=True))

    # -- record -------------------------------------------------------------
    def record(self, case: Case, *,
               energy_rtol: float = DEFAULT_ENERGY_RTOL) -> RecordResult:
        """Capture both twins, compare, and persist baseline + artifacts.

        The compare runs live, so every phase-2 decision the matcher made
        is persisted onto the artifacts (value digests + unfolding spectra;
        raw value chunks too unless the store is sketch-only) — the store
        can replay this exact comparison offline forever after.
        """
        if self.artifacts.readonly:
            raise BaselineError(
                "cannot record baselines into a readonly store "
                "(http mirror); record locally and `artifacts push`")
        art_a = self.session.capture(
            case.inefficient, case.make_args(), name=f"{case.id}-ineff",
            config=case.config_a,
            extra_meta={"zoo_case": case.id, "zoo_side": "ineff"})
        art_b = self.session.capture(
            case.efficient, case.make_args(), name=f"{case.id}-eff",
            config=case.config_b,
            extra_meta={"zoo_case": case.id, "zoo_side": "eff"})
        report = self.session.compare(art_a, art_b,
                                      output_rtol=case.output_rtol)
        baseline = Baseline.from_report(
            case, report, backend_id=self.session.backend.id,
            sample_seeds=art_a.sample_seeds, energy_rtol=energy_rtol)
        self._write_json(self.baseline_path(case.id), baseline.to_json())
        self._update_index(case.id, art_a.key, art_b.key)
        return RecordResult(baseline=baseline, report=report,
                            art_a=art_a, art_b=art_b)

    # -- check --------------------------------------------------------------
    def _offline_artifacts(self, case: Case):
        idx = self._load_index().get(case.id)
        if idx is None:
            raise BaselineError(
                f"{case.id}: no golden artifacts in {self.artifacts.root} — "
                "run `baseline record` (or a live `baseline check`) to "
                "populate the store before checking offline")
        try:
            return self.artifacts.load(idx["a"]), self.artifacts.load(idx["b"])
        except KeyError as e:
            raise BaselineError(
                f"{case.id}: golden artifact missing from store "
                f"({e.args[0]}); re-run `baseline record`") from None

    def check(self, case: Case, *, offline: bool = False) -> list[Drift]:
        """Replay one case and diff the findings against its baseline.

        ``offline=True`` loads the golden artifacts and never executes the
        candidates (the loaded artifacts are not even re-attached, so any
        attempted instrumented execution would raise).
        """
        expected = self.load(case.id)
        try:
            if offline:
                art_a, art_b = self._offline_artifacts(case)
            else:
                art_a = self.session.capture(
                    case.inefficient, case.make_args(),
                    name=f"{case.id}-ineff", config=case.config_a,
                    sample_seeds=expected.sample_seeds,
                    extra_meta={"zoo_case": case.id, "zoo_side": "ineff"})
                art_b = self.session.capture(
                    case.efficient, case.make_args(), name=f"{case.id}-eff",
                    config=case.config_b,
                    sample_seeds=expected.sample_seeds,
                    extra_meta={"zoo_case": case.id, "zoo_side": "eff"})
                # a live check (re)populates the golden store, so a
                # subsequent offline replay can run against exactly what was
                # just checked
                self._update_index(case.id, art_a.key, art_b.key)
        except StoreError as e:
            # a corrupt/unreachable golden store (the session is strict:
            # allow_degraded=False, so it surfaces instead of degrading) is
            # declared as drift — the check did NOT reproduce the baseline
            # and CI must say why
            return [Drift(case.id, "store",
                          "golden store reachable and intact",
                          f"{type(e).__name__}: {e}")]
        if art_a.backend_id != expected.backend_id:
            return [Drift(case.id, "backend_id", expected.backend_id,
                          art_a.backend_id)]
        try:
            report = self.session.compare(art_a, art_b,
                                          output_rtol=case.output_rtol)
        except ArtifactValueError as e:
            # the record-time compare memoized exactly the values a
            # bit-identical replay fetches, so needing MORE values IS
            # changed matcher behavior — report it as drift, never as
            # advice to re-record (that would bless the change unseen)
            return [Drift(case.id, "offline_replay",
                          "all phase-2 fetches served from the golden store",
                          f"unmaterialized fetch: {e}")]
        except StoreError as e:
            # a corrupt/unreachable golden store is declared as drift, not
            # silently degraded around: the check did NOT reproduce the
            # baseline and CI must say why
            return [Drift(case.id, "store",
                          "golden store reachable and intact",
                          f"{type(e).__name__}: {e}")]
        actual = Baseline.from_report(
            case, report, backend_id=art_a.backend_id,
            sample_seeds=art_a.sample_seeds, energy_rtol=expected.energy_rtol)
        return diff_baselines(expected, actual)

