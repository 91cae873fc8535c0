"""Magneton command-line interface.

Drives the Session/artifact API (core/session.py) from the shell::

  python -m repro.cli cases                         # list the case zoo
  python -m repro.cli capture c6-matpow:ineff       # capture one candidate
  python -m repro.cli compare c6-matpow:ineff c6-matpow:eff --json out.json
  python -m repro.cli rank c6-matpow:ineff c6-matpow:eff [SPEC ...]
  python -m repro.cli report out.json               # re-render stored JSON
  python -m repro.cli artifacts                     # list the store
  python -m repro.cli artifacts stats               # dedup / sketch coverage
  python -m repro.cli artifacts push --to file:///mnt/nfs/magneton
  python -m repro.cli artifacts pull --from http://mirror:8000
  python -m repro.cli artifacts migrate             # legacy .npz -> v3
  python -m repro.cli fleet status --store URI      # live-audit dashboard

Candidate SPECs are either zoo references ``<case-id>:<ineff|eff>``
(resolved through the registry in zoo/cases.py and captured on the case's
canonical inputs — repeated invocations hit the content-addressed store and
skip re-execution) or artifact keys / ``.npz`` paths produced by an earlier
``capture``.  The store root comes from ``--store`` (a path, ``file://``
URI, or readonly ``http(s)://`` mirror), ``$MAGNETON_STORE``, or
``~/.cache/magneton/artifacts``; ``--remote URI`` attaches a read-through
upstream so captures recorded elsewhere become local cache hits.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

from repro.compile_cache import enable_compile_cache
from repro.core.artifact import (ArtifactStore, ArtifactValueError,
                                 CandidateArtifact)
from repro.core.energy import backend_from_name
from repro.core.store import StoreReadOnlyError
from repro.core.report import Report
from repro.core.session import RankResult, Session
from repro.zoo import cases as zoo

_SIDES = zoo.SIDE_ALIASES


@dataclasses.dataclass
class _Resolved:
    artifact: CandidateArtifact
    output_rtol: float = 1e-2
    in_store: bool = True


def _maybe_attach_zoo(art: CandidateArtifact, session: Session
                      ) -> CandidateArtifact:
    """Re-attach a zoo-born loaded artifact to its case function so lazy
    phase-2 value fetches work (compare-by-key after a bare `capture`).

    Only when the session's backend matches the artifact's recorded one:
    re-capturing under a different backend would both ignore the stored
    pricing and pollute the store with a mismatched artifact.

    The provenance key check runs BEFORE any capture: the case is re-traced
    (cheap, no execution) and its content address compared against the
    artifact's recorded key.  A mismatch — stale provenance metadata, a
    changed case definition — returns the artifact untouched instead of
    capturing first and rejecting after, which used to leave the rejected
    re-capture orphaned in the store.
    """
    from repro.core.artifact import artifact_key
    from repro.core.graph import trace

    case_id = art.meta.get("zoo_case")
    side = art.meta.get("zoo_side")
    if (art.is_live or not case_id or side not in _SIDES
            or session.backend.id != art.backend_id):
        return art
    try:
        case = zoo.get_case(case_id)
    except KeyError:
        return art
    fn, _ = case.side(side)
    case_args = case.make_args()
    try:
        # one extra trace (capture re-traces internally on the accept path);
        # acceptable on this interactive, once-per-process CLI route — the
        # alternative is widening capture() to accept a pre-traced graph
        graph = trace(fn, *case_args, name=art.name)
    except Exception:
        return art
    if artifact_key(graph, case_args, art.sample_seeds,
                    session.backend.id) != art.key:
        return art
    return session.capture(fn, case_args, name=art.name,
                           config=art.config,
                           sample_seeds=art.sample_seeds,
                           extra_meta={"zoo_case": case_id,
                                       "zoo_side": side})


def _resolve_spec(spec: str, session: Session) -> _Resolved:
    """Resolve a candidate SPEC to an artifact (capturing zoo cases)."""
    if ":" in spec and not spec.endswith(".npz"):
        case_id, _, side = spec.rpartition(":")
        if side not in _SIDES:
            raise SystemExit(
                f"bad spec {spec!r}: side must be one of {sorted(_SIDES)}")
        try:
            case = zoo.get_case(case_id)
        except KeyError as e:
            raise SystemExit(f"error: {e.args[0]}") from None
        fn, config = case.side(side)
        art = session.capture(fn, case.make_args(),
                              name=f"{case.id}-{side}", config=config,
                              extra_meta={"zoo_case": case.id,
                                          "zoo_side": side})
        return _Resolved(art, output_rtol=case.output_rtol)
    if spec.endswith(".npz"):
        art = CandidateArtifact.load(Path(spec))
        return _Resolved(_maybe_attach_zoo(art, session), in_store=False)
    if session.store is not None and session.store.has(spec):
        art = session.store.load(spec)
        return _Resolved(_maybe_attach_zoo(art, session))
    raise SystemExit(
        f"cannot resolve {spec!r}: not a '<case>:<side>' zoo reference, "
        "an .npz path, or a key in the artifact store "
        f"({session.store.root if session.store else 'no store'})")


def _open_store(uri: str | None, remote: str | None = None,
                timeout: float | None = None) -> ArtifactStore:
    if remote and uri is not None and "://" in str(uri):
        # a URI store is itself remote-backed; silently ignoring --remote
        # would discard the user's read-through cache expectation
        raise SystemExit(
            "error: --remote needs a LOCAL --store path to cache into; "
            f"--store {uri!r} is already a remote URI")
    if uri is None:
        return (ArtifactStore(remote=remote, store_timeout=timeout)
                if remote else ArtifactStore())
    if remote:
        return ArtifactStore(uri, remote=remote, store_timeout=timeout)
    return ArtifactStore.from_uri(uri, store_timeout=timeout)


def _make_session(args) -> Session:
    return Session(backend=backend_from_name(args.backend),
                   store=_open_store(args.store,
                                     getattr(args, "remote", None),
                                     getattr(args, "store_timeout", None)),
                   num_input_samples=args.samples)


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--store", default=None,
                   help="artifact store root or URI (path, file:// or "
                        "readonly http(s):// mirror; default: "
                        "$MAGNETON_STORE or ~/.cache/magneton/artifacts)")
    p.add_argument("--remote", default=None, metavar="URI",
                   help="read-through upstream store: cache misses pull "
                        "manifests/chunks recorded elsewhere")
    p.add_argument("--store-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="connect/read deadline per http(s) store fetch "
                        "(default: $MAGNETON_STORE_TIMEOUT or 30); an "
                        "unreachable mirror fails typed instead of hanging")
    p.add_argument("--backend", default="analytic",
                   choices=("analytic", "replay", "hlo"))
    p.add_argument("--samples", type=int, default=2,
                   help="input samples per capture (Hypothesis 1 probes)")


def cmd_cases(args) -> int:
    listed = zoo.list_cases(category=args.category,
                            known=True if args.known else None)
    for c in listed:
        print(f"{c.id:24} {c.paper_id:16} {c.category:18} "
              f"{'known' if c.known else 'new':5}  {c.description}")
    print(f"{len(listed)} cases")
    return 0


def cmd_capture(args) -> int:
    session = _make_session(args)
    for spec in args.spec:
        res = _resolve_spec(spec, session)
        art = res.artifact
        hit = "cache-hit" if art.meta.get("cache_hit") else "captured"
        where = (session.store.path_for(art.key)
                 if res.in_store and session.store.has(art.key) else spec)
        print(f"{hit} {art.name}: key={art.key} nodes={len(art.graph.nodes)} "
              f"samples={art.num_samples} "
              f"energy={art.profile.total_energy_j:.4e} J -> {where}")
        if art.profile.hlo is not None:
            # attribution-quality monitoring: a rising residual fraction or
            # opaque-node count means per-op pricing is degrading
            s = art.profile.hlo.attribution_summary()
            print(f"  attribution: direct {s['direct_fraction']:.1%} of "
                  f"{s['instructions']} instrs, residual "
                  f"flops {s['residual_flop_fraction']:.2%} / "
                  f"bytes {s['residual_byte_fraction']:.2%}, "
                  f"opaque-nodes {s['opaque_nodes']}, "
                  f"fusion-splits {s['fusion_splits']}")
    return 0


def cmd_compare(args) -> int:
    session = _make_session(args)
    ra = _resolve_spec(args.spec_a, session)
    rb = _resolve_spec(args.spec_b, session)
    rtol = (args.output_rtol if args.output_rtol is not None
            else max(ra.output_rtol, rb.output_rtol))
    report = session.compare(ra.artifact, rb.artifact, output_rtol=rtol)
    print(report.render())
    if args.json:
        Path(args.json).write_text(report.to_json())
        print(f"wrote {args.json}")
    return 0 if not args.expect_waste or report.waste_findings else 1


def cmd_rank(args) -> int:
    session = _make_session(args)
    resolved = [_resolve_spec(s, session) for s in args.spec]
    if len(resolved) < 2:
        raise SystemExit("rank needs at least two candidate SPECs")
    rtol = (args.output_rtol if args.output_rtol is not None
            else max(r.output_rtol for r in resolved))
    result = session.rank([r.artifact for r in resolved], output_rtol=rtol)
    print(result.render())
    if args.json:
        Path(args.json).write_text(result.to_json())
        print(f"wrote {args.json}")
    return 0


def cmd_report(args) -> int:
    data = json.loads(Path(args.path).read_text())
    if data.get("kind") == "rank":
        print(RankResult.from_json(data).render())
    elif data.get("kind") == "patch":
        from repro.optimize import PatchReport
        print(PatchReport.from_json(data).render())
    else:
        print(Report.from_json(data).render(max_findings=args.max_findings))
    return 0


def cmd_optimize(args) -> int:
    """Propose + verify inverse rewrites for a wasteful program.

    SPEC is either ``<mutation>:<program>`` (a generated scenario: the
    clean program is mutated, compared for a diagnosis, and the mutant
    optimized — the full detect→transform→verify loop) or a zoo
    ``<case>:<side>`` reference (every rewrite is attempted; no prior
    diagnosis orients the proposal).
    """
    from repro.optimize import optimize
    from repro.testing.mutate import (MUTATIONS, InapplicableMutationError,
                                      clean_programs, make_mutant)

    session = _make_session(args)
    spec = args.spec
    if ":" not in spec:
        raise SystemExit(
            f"bad spec {spec!r}: expected '<mutation>:<program>' "
            f"(mutations: {sorted(MUTATIONS)}) or a zoo '<case>:<side>'")
    left, _, right = spec.partition(":")
    diagnosis = None
    config = None
    if left in MUTATIONS:
        progs = {p.name: p for p in clean_programs()}
        if right not in progs:
            raise SystemExit(
                f"unknown clean program {right!r}; one of {sorted(progs)}")
        prog = progs[right]
        fargs = prog.make_args()
        try:
            fn, sites = make_mutant(prog.fn, MUTATIONS[left](), fargs)
        except InapplicableMutationError as e:
            raise SystemExit(f"error: {e}") from None
        name = fn.__name__
        # diagnose first so the proposal is oriented the way a real run
        # would be: detector flags the waste, its subkind picks the rewrite
        clean_art = session.capture(prog.fn, fargs, name=prog.name)
        mut_art = session.capture(fn, fargs, name=name)
        rep = session.compare(mut_art, clean_art, output_rtol=1e-2)
        waste = [f for f in rep.waste_findings if f.wasteful_side == "A"]
        diagnosis = next(
            (f.diagnosis for f in waste
             if f.diagnosis and f.diagnosis.subkind),
            waste[0].diagnosis if waste else None)
        if diagnosis is None:
            print("note: detector found no waste region; trying every "
                  "rewrite without a diagnosis", file=sys.stderr)
    else:
        case_id, side = left, right
        if side not in _SIDES:
            raise SystemExit(
                f"bad spec {spec!r}: not a mutation in {sorted(MUTATIONS)} "
                f"and side {side!r} not in {sorted(_SIDES)}")
        try:
            case = zoo.get_case(case_id)
        except KeyError as e:
            raise SystemExit(f"error: {e.args[0]}") from None
        fn, config = case.side(side)
        fargs = case.make_args()
        name = f"{case.id}-{side}"
    patch = optimize(fn, fargs, session=session, name=name,
                     diagnosis=diagnosis,
                     rewrite_names=args.rewrite or None,
                     output_rtol=args.output_rtol, config=config)
    print(patch.render())
    if args.json:
        Path(args.json).write_text(patch.to_json())
        print(f"wrote {args.json}")
    return 0 if not args.expect_win or patch.best is not None else 1


def _parse_bytes(text: str) -> int:
    """'500K' / '10M' / '1G' / plain integer byte counts."""
    t = text.strip().upper()
    mult = {"K": 1024, "M": 1024 ** 2, "G": 1024 ** 3}.get(t[-1:], 1)
    return int(float(t[:-1] if mult > 1 else t) * mult)


def cmd_artifacts(args) -> int:
    store = _open_store(args.store,
                        timeout=getattr(args, "store_timeout", None))
    action = getattr(args, "action", None)
    if action == "prune":
        verb = "would delete" if args.dry_run else "deleted"
        if args.quarantine:
            try:
                evicted = store.prune_quarantine(
                    max_bytes=(_parse_bytes(args.max_bytes)
                               if args.max_bytes is not None else None),
                    dry_run=args.dry_run)
            except ValueError as e:
                raise SystemExit(f"error: {e}") from None
            for name in evicted:
                print(f"{verb} quarantine/{name}")
            print(f"{verb} {len(evicted)} quarantined files; quarantine now "
                  f"{store.quarantine_bytes() / 1024:.1f} KiB")
            return 0
        try:
            deleted = store.prune(
                max_bytes=(_parse_bytes(args.max_bytes)
                           if args.max_bytes is not None else None),
                keep_latest=args.keep_latest, dry_run=args.dry_run)
        except ValueError as e:
            raise SystemExit(f"error: {e}") from None
        for key in deleted:
            print(f"{verb} {key}")
        print(f"{verb} {len(deleted)} artifacts; store {store.root} now "
              f"{store.total_bytes() / 1024:.1f} KiB")
        return 0
    if action == "stats":
        s = store.stats()
        print(f"artifacts: {s['artifacts']} manifests "
              f"(+{s['legacy_npz']} legacy .npz)")
        print(f"chunks: {s['chunk_count']} "
              f"({s['chunk_bytes'] / 1024:.1f} KiB)")
        bc = s.get("block_cache", {})
        print(f"block evidence: {s.get('block_entries', 0)} block + "
              f"{s.get('profile_entries', 0)} profile + "
              f"{s.get('hlo_entries', 0)} hlo entries "
              f"({s.get('block_evidence_manifest_bytes', 0) / 1024:.1f} KiB "
              f"manifests)")
        print(f"block cache (this process): "
              f"{bc.get('block_hits', 0)} hits / "
              f"{bc.get('block_misses', 0)} misses; profile "
              f"{bc.get('profile_hits', 0)} hits / "
              f"{bc.get('profile_misses', 0)} misses")
        print(f"values: {s['values_total']} recorded, "
              f"{s['values_sketch_only']} sketch-only "
              f"({s['sketch_only_fraction']:.1%}); "
              f"{s['spectra_entries']} spectra entries")
        print(f"physical bytes: {s['physical_bytes'] / 1024:.1f} KiB; "
              f"monolithic-equivalent: "
              f"{s['monolithic_bytes'] / 1024:.1f} KiB")
        print(f"dedup ratio: {s['dedup_ratio']:.2f}x")
        if args.json:
            Path(args.json).write_text(json.dumps(s, indent=2))
            print(f"wrote {args.json}")
        return 0
    if action == "push":
        res = store.push(args.to, keys=args.key or None)
        print(f"pushed {res['manifests']} manifests to {args.to}: "
              f"{res['chunks_copied']} chunks copied "
              f"({res['bytes_copied'] / 1024:.1f} KiB), "
              f"{res['chunks_skipped']} already present")
        return 0
    if action == "pull":
        res = store.pull(getattr(args, "from"), keys=args.key or None)
        print(f"pulled {res['manifests']} manifests from "
              f"{getattr(args, 'from')}: {res['chunks_copied']} chunks "
              f"copied ({res['bytes_copied'] / 1024:.1f} KiB), "
              f"{res['chunks_skipped']} already present")
        return 0
    if action == "migrate":
        res = store.migrate(args.key or None,
                            delete_legacy=not args.keep_legacy)
        print(f"migrated {res['migrated']} legacy .npz artifacts to the "
              f"chunked v3 layout ({res['skipped']} skipped); "
              f"store {store.root} now {store.total_bytes() / 1024:.1f} KiB")
        return 0
    entries = store.entries()
    for e in entries:
        values = (f"values={e['cached_values']:4}"
                  if not e.get("sketch_only_values")
                  else f"values={e['cached_values']:4}"
                       f"+{e['sketch_only_values']}s")
        print(f"{e['key']:22} {e['name']:28} backend={e['backend']:12} "
              f"nodes={e['nodes']:5} samples={e['samples']} "
              f"{values} {e['bytes'] / 1024:.1f} KiB")
    print(f"{len(entries)} artifacts in {store.root}")
    return 0


def cmd_fleet(args) -> int:
    from repro.audit.fleet import fleet_status, render_fleet_status
    from repro.core.store import StoreError

    if args.store is None:
        raise SystemExit("error: fleet status needs --store URI "
                         "(the shared store your engines write to)")
    try:
        status = fleet_status(args.store,
                              timeout=getattr(args, "store_timeout", None))
    except StoreError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        Path(args.json).write_text(json.dumps(status, indent=2,
                                              sort_keys=True))
        print(f"wrote {args.json}")
    print(render_fleet_status(status))
    if args.fail_on_alarm and status["total_alarms"]:
        return 1
    return 0


def _baseline_cases(names) -> list:
    if not names:
        return zoo.list_cases()
    try:
        return [zoo.get_case(n) for n in names]
    except KeyError as e:
        raise SystemExit(f"error: {e.args[0]}") from None


def cmd_baseline(args) -> int:
    from repro.testing.baselines import (DEFAULT_ENERGY_RTOL, BaselineError,
                                         BaselineStore)

    session = Session(backend=backend_from_name(args.backend),
                      num_input_samples=args.samples)
    artifact_store = (ArtifactStore.from_uri(
        args.store, store_timeout=getattr(args, "store_timeout", None))
        if args.store is not None else None)
    store = BaselineStore(
        args.dir, session=session, artifact_store=artifact_store,
        sketch_only=not getattr(args, "full_values", False))
    cases = _baseline_cases(args.case)
    if args.action == "record":
        rtol = (args.energy_rtol if args.energy_rtol is not None
                else DEFAULT_ENERGY_RTOL)
        for case in cases:
            res = store.record(case, energy_rtol=rtol)
            b = res.baseline
            kinds = sorted({w.kind for w in b.waste if w.kind}) or ["-"]
            print(f"recorded {case.id}: detected={b.detected} "
                  f"waste={len(b.waste)} kind={','.join(kinds)} "
                  f"E_A={b.total_energy_a_j:.4e} J "
                  f"E_B={b.total_energy_b_j:.4e} J")
        print(f"{len(cases)} baselines -> {store.root}")
        return 0
    # check: always visit every case — one missing/corrupt golden must not
    # mask the drift status of the cases after it
    drifted = errors = 0
    for case in cases:
        try:
            drifts = store.check(case, offline=args.offline)
        except BaselineError as e:
            errors += 1
            print(f"ERROR {case.id}: {e}", file=sys.stderr)
            continue
        except Exception as e:                # corrupt JSON/.npz and the like
            errors += 1
            print(f"ERROR {case.id}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        if drifts:
            drifted += 1
            print(f"DRIFT {case.id}: {len(drifts)} fields")
            for d in drifts:
                print(f"    {d}")
        else:
            print(f"ok    {case.id}")
    mode = "offline replay" if args.offline else "live"
    print(f"baseline check ({mode}): "
          f"{len(cases) - drifted - errors}/{len(cases)} cases clean")
    return 2 if errors else (1 if drifted else 0)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.cli",
        description="Magneton differential energy debugging CLI")
    sub = p.add_subparsers(dest="cmd", required=True)

    pc = sub.add_parser("cases", help="list the energy-waste case zoo")
    pc.add_argument("--category", default=None)
    pc.add_argument("--known", action="store_true",
                    help="only Table-1 (known) cases")
    pc.set_defaults(fn=cmd_cases)

    pcap = sub.add_parser("capture",
                          help="capture candidate artifacts into the store")
    pcap.add_argument("spec", nargs="+", metavar="SPEC")
    _add_common(pcap)
    pcap.set_defaults(fn=cmd_capture)

    pcm = sub.add_parser("compare", help="compare two candidate artifacts")
    pcm.add_argument("spec_a", metavar="SPEC_A")
    pcm.add_argument("spec_b", metavar="SPEC_B")
    pcm.add_argument("--json", default=None, help="also write Report JSON")
    pcm.add_argument("--output-rtol", type=float, default=None)
    pcm.add_argument("--expect-waste", action="store_true",
                     help="exit 1 if no energy-waste region is found")
    _add_common(pcm)
    pcm.set_defaults(fn=cmd_compare)

    pr = sub.add_parser("rank", help="N-way differential ranking")
    pr.add_argument("spec", nargs="+", metavar="SPEC")
    pr.add_argument("--json", default=None, help="also write RankResult JSON")
    pr.add_argument("--output-rtol", type=float, default=None)
    _add_common(pr)
    pr.set_defaults(fn=cmd_rank)

    prp = sub.add_parser("report",
                         help="re-render a stored compare/rank/patch JSON")
    prp.add_argument("path")
    prp.add_argument("--max-findings", type=int, default=10)
    prp.set_defaults(fn=cmd_report)

    po = sub.add_parser(
        "optimize",
        help="propose + verify inverse rewrites for a wasteful program")
    po.add_argument("spec", metavar="SPEC",
                    help="'<mutation>:<program>' scenario (diagnose the "
                         "mutant, then optimize it) or a zoo "
                         "'<case>:<side>' reference (try every rewrite)")
    po.add_argument("--rewrite", action="append", default=None,
                    metavar="NAME",
                    help="only attempt these rewrites (repeatable; "
                         "default: diagnosed subkind first, rest ride "
                         "along)")
    po.add_argument("--json", default=None, help="also write PatchReport "
                                                 "JSON")
    po.add_argument("--output-rtol", type=float, default=None,
                    help="override the per-rewrite functional-equivalence "
                         "tolerance")
    po.add_argument("--expect-win", action="store_true",
                    help="exit 1 unless some candidate verified strictly "
                         "cheaper")
    _add_common(po)
    po.set_defaults(fn=cmd_optimize)

    pa = sub.add_parser("artifacts",
                        help="list, GC, transfer or migrate the store")
    pa.add_argument("--store", default=None)
    pa.add_argument("--store-timeout", type=float, default=None,
                    metavar="SECONDS")
    pa.set_defaults(fn=cmd_artifacts, action=None)
    pasub = pa.add_subparsers(dest="action")

    def _store_sub(name: str, help_: str) -> argparse.ArgumentParser:
        px = pasub.add_parser(name, help=help_)
        # SUPPRESS: when --store is not given after the action, the
        # subparser must not plant its own default over a value parsed at
        # the `artifacts` level (`artifacts --store X prune` would
        # otherwise act on the DEFAULT store)
        px.add_argument("--store", default=argparse.SUPPRESS)
        px.set_defaults(fn=cmd_artifacts)
        return px

    pap = _store_sub("prune", "GC the store, oldest first (refcount-aware)")
    pap.add_argument("--max-bytes", default=None, metavar="N[K|M|G]",
                     help="prune oldest artifacts until the store fits "
                          "(with --quarantine: until the quarantine fits)")
    pap.add_argument("--keep-latest", type=int, default=0,
                     help="never prune the N most recent artifacts")
    pap.add_argument("--quarantine", action="store_true",
                     help="prune the corruption-quarantine directory "
                          "instead of the artifact store (oldest first; "
                          "no --max-bytes empties it)")
    pap.add_argument("--dry-run", action="store_true")

    pas = _store_sub("stats", "dedup / sketch-only accounting")
    pas.add_argument("--json", default=None, help="also write stats JSON")

    papu = _store_sub("push", "copy manifests + missing chunks to a mirror")
    papu.add_argument("--to", required=True, metavar="URI",
                      help="destination store (path or file:// URI)")
    papu.add_argument("key", nargs="*", metavar="KEY",
                      help="keys to push (default: everything)")

    papl = _store_sub("pull", "fetch manifests + missing chunks from a store")
    papl.add_argument("--from", required=True, metavar="URI", dest="from",
                      help="source store (path, file:// or http(s):// URI)")
    papl.add_argument("key", nargs="*", metavar="KEY",
                      help="keys to pull (default: everything)")

    pam = _store_sub("migrate",
                     "convert legacy .npz entries to the chunked v3 layout")
    pam.add_argument("--keep-legacy", action="store_true",
                     help="leave the source .npz files in place")
    pam.add_argument("key", nargs="*", metavar="KEY",
                     help="keys to migrate (default: every legacy entry)")

    pf = sub.add_parser(
        "fleet", help="cross-engine audit dashboard over a shared store")
    pfsub = pf.add_subparsers(dest="action", required=True)
    pfs = pfsub.add_parser(
        "status", help="per-class energy trend, drift alarms, sample counts "
                       "and degradation rungs across engines")
    pfs.add_argument("--store", default=None, metavar="URI",
                     help="the shared fleet store (path, file:// or "
                          "http(s):// URI) engines write audit state to")
    pfs.add_argument("--store-timeout", type=float, default=None,
                     metavar="SECONDS")
    pfs.add_argument("--json", default=None,
                     help="also write the aggregated status JSON")
    pfs.add_argument("--fail-on-alarm", action="store_true",
                     help="exit 1 when any engine reports a drift alarm")
    pfs.set_defaults(fn=cmd_fleet)

    pb = sub.add_parser(
        "baseline", help="golden energy baselines: record / check drift")
    pbsub = pb.add_subparsers(dest="action", required=True)
    for action in ("record", "check"):
        px = pbsub.add_parser(action)
        px.add_argument("case", nargs="*", metavar="CASE",
                        help="zoo case ids (default: every registered case)")
        px.add_argument("--dir", default="tests/baselines",
                        help="baseline root (JSON expectations + index.json; "
                             "golden artifacts default to <dir>/store)")
        px.add_argument("--store", default=None, metavar="URI",
                        help="golden artifact store override: a path, a "
                             "file:// NFS mirror, or a readonly http(s):// "
                             "mirror for offline checks")
        px.add_argument("--store-timeout", type=float, default=None,
                        metavar="SECONDS",
                        help="connect/read deadline per http(s) store fetch "
                             "(default: $MAGNETON_STORE_TIMEOUT or 30)")
        px.add_argument("--backend", default="analytic",
                        choices=("analytic", "replay", "hlo"))
        px.add_argument("--samples", type=int, default=2,
                        help="input samples per capture (Hypothesis 1 probes)")
        px.set_defaults(fn=cmd_baseline)
    pbsub.choices["record"].add_argument(
        "--energy-rtol", type=float, default=None,
        help="declared tolerance for the recorded energy fields")
    pbsub.choices["record"].add_argument(
        "--full-values", action="store_true",
        help="persist raw value chunks too (default: sketch-only manifests "
             "— digests + spectra replay every recorded match)")
    pbsub.choices["check"].add_argument(
        "--offline", action="store_true",
        help="replay from golden artifacts only; no instrumented execution")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:      # e.g. `... | head` closed stdout
        return 0
    except ArtifactValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except ValueError as e:
        # predictable user errors from compare/rank (backend or sample-seed
        # mismatch, not-the-same-task gate) — message, not a traceback
        print(f"error: {e}", file=sys.stderr)
        return 2
    except (PermissionError, StoreReadOnlyError) as e:
        # writes against a readonly (http mirror) store
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
