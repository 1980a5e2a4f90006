#!/usr/bin/env python
"""perf_ledger CLI: the cross-run performance ledger and its CI gate.

The perf twin of tools/memory_anatomy.py --check: every bench /
serving_bench / multichip receipt appends ONE JSONL record to the
ledger (numeric leaves flattened, keyed by a program/config
fingerprint), and a committed baseline gates regressions per metric
with a DIRECTION (higher-better tokens/s and goodput, lower-better
p99 TTFT and wire bytes, exact-better compile/recompile counts) and a
TOLERANCE. Imports no jax — ingest/check/trend run on any triage host.

Modes (combinable; order: ingest -> inflate -> write-baseline
-> check -> trend):
  --ingest FILE...    append records from receipt artifacts (driver
                      wrappers with "parsed", multichip probes, or raw
                      emit_report JSON / last line of a log). Skips
                      runs whose id is already ledgered (idempotent).
  --check [RECEIPT]   gate a receipt (or, with no file, the NEWEST
                      ledger record per fingerprint) against the
                      baseline: exit 1 naming metric + run + delta.
  --write-baseline    re-anchor on the newest record per fingerprint.
  --trend             render the per-fingerprint trajectory
                      (sparkline + per-run values; --metric selects a
                      series, default the headline "value").
  --inflate KEY:X     multiply a metric by X on a COPY before
                      checking — the drill lever the regression test
                      uses to prove the gate trips (the ledger and
                      baseline only ever persist REAL numbers).

Always prints a final ``perf_ledger: {json}`` receipt line.

Usage:
  python tools/perf_ledger.py --check                    # CI gate
  python tools/perf_ledger.py --ingest BENCH.json --check
  python tools/perf_ledger.py --trend
  python tools/perf_ledger.py --check --inflate value:0.5  # must rc 1
"""
import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

# the module by FILE PATH, never through the paddle_tpu package —
# importing the framework pulls jax, and this CLI's contract is to
# run on triage hosts where jax is wedged or absent. ONE copy of the
# loader (tpu_doctor owns it; tpu_doctor itself is stdlib-only).
import tpu_doctor  # noqa: E402

pl = tpu_doctor._load_perf_ledger()

DEFAULT_LEDGER = os.path.join(REPO, "tools", "perf_ledger.jsonl")
DEFAULT_BASELINE = os.path.join(REPO, "tools", "perf_baseline.json")


def _load_artifact(path: str):
    """An artifact file: JSON, or a log whose LAST parseable line is
    the receipt (bench/serving_bench print one JSON line)."""
    with open(path) as f:
        text = f.read().strip()
    try:
        return json.loads(text)
    except ValueError:
        pass
    for line in reversed(text.splitlines()):
        line = line.strip()
        # tool receipts print as "<name>: {json}"
        line = re.sub(r"^[a-z_]+:\s*(?=\{)", "", line)
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise SystemExit(f"{path}: no JSON receipt found")


def _source_of(path: str) -> str:
    name = os.path.basename(path).lower()
    if "multichip" in name:
        return "multichip"
    if "serving" in name:
        return "serving_bench"
    return "bench"


def _run_id_of(path: str, doc) -> str:
    """Stable run id so re-ingesting an artifact is a no-op: the
    round-numbered repo artifacts become bench-r01 style ids, ad-hoc
    receipts fall back to the filename."""
    n = doc.get("n") if isinstance(doc, dict) else None
    src = _source_of(path)
    if isinstance(n, int):
        return f"{src}-r{n:02d}"
    m = re.search(r"_r(\d+)", os.path.basename(path))
    if m:
        return f"{src}-r{int(m.group(1)):02d}"
    return f"{src}-{os.path.splitext(os.path.basename(path))[0]}"


def ingest(paths, ledger_path: str, verbose: bool = True):
    have = {r.get("run") for r in pl.load_ledger(ledger_path)}
    added = []
    for path in paths:
        doc = _load_artifact(path)
        run = _run_id_of(path, doc)
        if run in have:
            if verbose:
                print(f"# {path}: run {run} already ledgered, "
                      "skipping", flush=True)
            continue
        ts = None
        try:
            ts = round(os.path.getmtime(path), 3)
        except OSError:
            pass
        # the filename's round number orders records even when the
        # artifact embeds none (MULTICHIP_r0*) — mtime is not stable
        # across checkouts, so it must never decide "latest"
        m = re.search(r"_r(\d+)", os.path.basename(path))
        rec = pl.record_from_artifact(
            doc, source=_source_of(path), run=run, ts=ts,
            round_n=int(m.group(1)) if m else None)
        if rec is None:
            if verbose:
                print(f"# {path}: nothing numeric to ledger, "
                      "skipping", flush=True)
            continue
        pl.append_record(ledger_path, rec)
        have.add(run)
        added.append(rec)
        if verbose:
            print(f"# ledgered {run} ({rec['label']}, "
                  f"{len(rec['metrics'])} metrics)", flush=True)
    return added


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--ledger", default=DEFAULT_LEDGER)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--ingest", nargs="+", default=None,
                    metavar="FILE", help="append receipt artifacts")
    ap.add_argument("--check", nargs="?", const="", default=None,
                    metavar="RECEIPT",
                    help="gate a receipt (default: newest ledger "
                         "record per fingerprint) against the "
                         "baseline; exit 1 on regression")
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override every metric's tolerance")
    ap.add_argument("--trend", action="store_true",
                    help="render the cross-run trajectory")
    ap.add_argument("--metric", default=None,
                    help="series for --trend (default: headline "
                         "'value')")
    ap.add_argument("--inflate", default="", metavar="KEY:FACTOR",
                    help="multiply a metric on a COPY before checking "
                         "(regression-drill lever), e.g. value:0.5")
    args = ap.parse_args(argv)

    if args.ingest:
        ingest(args.ingest, args.ledger)

    records = pl.load_ledger(args.ledger)

    if args.write_baseline:
        if not records:
            raise SystemExit("--write-baseline: ledger is empty")
        pl.write_ledger_baseline(
            records, args.baseline,
            tolerance=(pl.DEFAULT_TOLERANCE if args.tolerance is None
                       else args.tolerance))
        print(f"perf baseline re-anchored: "
              f"{len(pl.latest_by_fingerprint(records))} "
              f"fingerprint(s) -> {args.baseline}", flush=True)

    findings = []
    rc = 0
    checked_runs = []
    if args.check is not None:
        if args.check:
            doc = _load_artifact(args.check)
            rec = pl.record_from_artifact(
                doc, source=_source_of(args.check),
                run=_run_id_of(args.check, doc))
            if rec is None:
                raise SystemExit(
                    f"--check {args.check}: nothing numeric to gate")
            to_check = [rec]
        else:
            to_check = list(pl.latest_by_fingerprint(records).values())
            if not to_check:
                raise SystemExit("--check: ledger is empty and no "
                                 "receipt given")
        # the drill lever inflates a COPY — the ledger/baseline only
        # ever persist real numbers (memory_anatomy's discipline)
        inflate_specs = [s for s in args.inflate.split(",")
                         if s.strip()]
        if inflate_specs:
            to_check = [dict(r, metrics=dict(r["metrics"]))
                        for r in to_check]
        for spec in inflate_specs:
            key, _, factor = spec.partition(":")
            f = float(factor or 1.0)
            hit = False
            for r in to_check:
                if key in r["metrics"]:
                    r["metrics"][key] = r["metrics"][key] * f
                    hit = True
            if not hit:
                raise SystemExit(f"--inflate: metric {key!r} not in "
                                 "any checked run")
        baseline = pl.load_ledger_baseline(args.baseline)
        for r in to_check:
            checked_runs.append(r.get("run"))
            findings.extend(pl.check_record(r, baseline,
                                            tolerance=args.tolerance))
        # calibration-table staleness rides every --check: a planner
        # audit that fell back to analytic constants (or a table
        # committed for a different mesh) is named loudly here, the
        # same place the exact-better calibration.match gate trips
        cal_table = None
        cal_path = os.environ.get(
            "PD_COST_CALIBRATION",
            os.path.join(REPO, "tools", "cost_calibration.json"))
        if os.path.exists(cal_path):
            try:
                with open(cal_path) as fh:
                    cal_table = json.load(fh)
            except ValueError:
                cal_table = None
        findings.extend(pl.check_calibration(records, cal_table))
        for f in findings:
            print(f.summary(), flush=True)
        rc = 1 if any(f.severity == "error" for f in findings) else 0

    if args.trend:
        print(pl.render_trend(records, metric=args.metric), flush=True)

    groups = pl.trend(records)
    summary = {
        "ledger": args.ledger,
        "records": len(records),
        "fingerprints": len(groups),
        "rounds": max((len(g["runs"]) for g in groups.values()),
                      default=0),
        "checked_runs": checked_runs,
        "findings": len(findings),
        "regressions": sum(1 for f in findings
                           if f.severity == "error"),
        "baseline": (args.baseline
                     if (args.check is not None
                         or args.write_baseline) else None),
        "ok": rc == 0,
    }
    print("perf_ledger:", json.dumps(summary), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
