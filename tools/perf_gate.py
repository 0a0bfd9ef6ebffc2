"""The perf regression gate: one honest trajectory over BENCH_*.json.

Every perf round leaves a ``BENCH_r<NN>.json`` artifact (bench.py's
record, usually inside the driver's ``{n, cmd, rc, tail, parsed}``
wrapper; judge re-measurements are bare records).  This tool folds ALL
of them into one per-metric trajectory and gates on it:

- records are re-audited through the PR 6 trust verdicts
  (``TimingAuditor``): a record carrying its own ``trust`` verdict
  keeps it, an older record claiming a platform is re-audited, and a
  pure host-side A/B ratio record (no platform/timing claim -- the
  BENCH_SERVE / BENCH_QCOMM / BENCH_PIPELINE speedups, the
  BENCH_SERVE_INT8 fp32-vs-int8 serving ratios, the BENCH_DECODE
  ``serving_decode_tokens_ratio`` /
  ``serving_paged_kv_bytes_ratio`` /
  ``serving_prefix_prefill_saved`` and the BENCH_WIRE transport A/Bs
  ``fleet_wire_rps_ratio`` -- binary-over-pickle requests/sec at the
  same closed-loop load -- and ``fleet_wire_bytes_ratio`` --
  fp32-over-int8 staged-weight bytes on the wire) is classed ``ratio``
  and is baseline-eligible: an int8 serving regression trips the gate
  exactly like an MFU regression;
- ``superseded`` records (BENCH_r02's async-dispatch artifact) and
  ``invalid:*`` / ``suspect:*`` verdicts are SHOWN in the trajectory
  but excluded from baselines -- an untrusted number can neither set
  the bar nor claim to clear it;
- the gate compares each metric's newest baseline-eligible record
  against the best earlier one: a drop beyond ``--tolerance`` exits
  nonzero, naming the regression.  ``--check FILE`` gates candidate
  record(s) (a fresh bench run) against the checked-in history without
  adding them to it -- the CI spelling;
- metrics are direction-classed: most are higher-is-better
  (images/sec, tokens/sec, speedup ratios), but PEAK-BYTES metrics
  (``*_bytes`` -- KV-cache or activation memory at fixed concurrency,
  the ROADMAP item 3 bench legs) are lower-is-better: for those the
  BEST history entry is the MINIMUM and a candidate above the
  tolerance ceiling trips the gate.  A record may also carry an
  explicit ``direction: "lower"|"higher"`` field, which wins over the
  name heuristic.  (``*_ratio`` / ``*_saved`` names stay
  higher-is-better even when they measure bytes -- the paged-KV
  ``serving_paged_kv_bytes_ratio`` is a reduction factor.)

    python -m tools.perf_gate                        # gate the repo
    python -m tools.perf_gate --check BENCH_new.json # gate a candidate
    python -m tools.perf_gate --format json          # machine-readable

Like ``tools/obs_report.py`` this imports no jax (``profiling.py`` is
spec-loaded): the gate runs anywhere the artifacts were copied.
"""

import argparse
import glob
import importlib.util
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_pspec = importlib.util.spec_from_file_location(
    "_gate_profiling",
    os.path.join(REPO, "bigdl_tpu", "observability", "profiling.py"))
_profiling = importlib.util.module_from_spec(_pspec)
_pspec.loader.exec_module(_profiling)
TimingAuditor = _profiling.TimingAuditor

#: trust classes a record may hold after re-audit; ``ratio`` is this
#: tool's addition: a host-side A/B ratio that never claimed a device
#: measurement, so the timing verdicts do not apply to it
TRUST_BASELINE_OK = ("trusted", "ratio")


def _round_key(path):
    """``BENCH_r02_judge.json`` -> (2, 1, name): judge/addendum files
    sort right after the round they re-measure."""
    name = os.path.basename(path)
    m = re.search(r"_r(\d+)", name)
    rnd = int(m.group(1)) if m else -1
    sub = 0 if re.fullmatch(r"BENCH_r\d+\.json", name) else 1
    return (rnd, sub, name)


def _round_label(path):
    name = os.path.basename(path)
    return re.sub(r"^BENCH_|\.json$", "", name)


def _record_lines(tail):
    """Bench records printed to the tail: every JSON line carrying a
    ``metric``, with pre-stage ``incomplete`` diagnostics dropped
    (bench prints those so a killed run still leaves evidence; a later
    line supersedes them by contract)."""
    records = []
    for ln in (tail or "").splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if not isinstance(rec, dict) or "metric" not in rec:
            continue
        extra = rec.get("extra") or {}
        if "incomplete" in str(extra.get("error", "")):
            continue
        records.append(rec)
    return records


def load_bench_file(path):
    """-> (records, note).  ``records`` is possibly empty (a round that
    died before printing anything still appears in the trajectory, as
    the note -- an empty round is evidence too)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [], f"unreadable: {e}"
    if not isinstance(doc, dict):
        return [], "unrecognized artifact shape"
    if "metric" in doc:                       # bare record (judge files)
        return [dict(doc)], None
    # driver wrapper: {n, cmd, rc, tail, parsed, superseded?}
    records = _record_lines(doc.get("tail"))
    if not records and isinstance(doc.get("parsed"), dict) \
            and "metric" in doc["parsed"]:
        records = [dict(doc["parsed"])]
    if doc.get("superseded"):
        for rec in records:
            rec["superseded"] = True
            rec["superseded_reason"] = doc.get("superseded_reason")
    if not records:
        return [], f"no record (rc={doc.get('rc')})"
    return records, None


def classify_trust(record):
    """The record's trust class for baseline purposes.

    A record that stamped its own verdict (PR 6 onward) keeps it; one
    that claims a platform (it measured a device) is re-audited through
    ``TimingAuditor.audit_record``; one claiming neither platform nor
    per-step timing is a host-side A/B ``ratio`` -- the auditor's
    device checks do not apply, and the ratio is reproducible evidence.

    A bench manifest confessing always-sample tracing overrides even
    the record's own stamp: every request paid span buffering and a
    forced traces.jsonl flush, so the number measures tracing, not the
    serving path (``invalid:traced``).  Records that predate the
    manifest carry no ``tracing`` block and are unaffected.
    """
    extra = record.get("extra", record) or {}
    tracing = extra.get("tracing") or {}
    if tracing.get("always_sample"):
        return "invalid:traced"
    if record.get("trust"):
        return str(record["trust"])
    if extra.get("platform") is None and \
            extra.get("sec_per_step_blocked") is None and \
            extra.get("sec_per_step") is None:
        return "ratio"
    return TimingAuditor().audit_record(record)["trust"]


def metric_direction(metric, record=None):
    """``"higher"`` or ``"lower"`` -- which way this metric improves.

    An explicit ``direction`` field on the record wins.  Otherwise the
    name decides: ``*_ratio`` / ``*_saved`` are improvement factors
    (higher), and ``*_bytes`` / ``*_peak`` are memory footprints
    (lower) -- a KV-cache or activation-memory record regresses by
    GROWING, unlike every throughput metric.  BENCH_r09's families pin
    both arms: ``*_kv_peak_bytes`` (int8 pool footprint, lower) and
    ``*_spec_tokens_ratio`` (speculative tokens/s factor, higher),
    with pins in tests/test_perf_gate.py."""
    rec_dir = (record or {}).get("direction")
    if rec_dir in ("lower", "higher"):
        return rec_dir
    name = str(metric or "")
    if name.endswith("_ratio") or name.endswith("_saved"):
        return "higher"
    if name.endswith("_bytes") or "_peak_bytes" in name \
            or name.endswith("_peak"):
        return "lower"
    return "higher"


def _entry(record, rnd_label, source):
    value = record.get("value")
    trust = classify_trust(record)
    superseded = bool(record.get("superseded"))
    finite = isinstance(value, (int, float)) and math.isfinite(value)
    return {
        "round": rnd_label,
        "file": source,
        "metric": record.get("metric"),
        "value": value if finite else None,
        "unit": record.get("unit"),
        "vs_baseline": record.get("vs_baseline"),
        "trust": trust,
        "superseded": superseded,
        "direction": metric_direction(record.get("metric"), record),
        # a baseline must be a real, trusted, non-superseded number
        "baseline_eligible": (finite and not superseded
                              and trust in TRUST_BASELINE_OK),
    }


def build_trajectory(bench_dir, extra_files=()):
    """-> {"metrics": {metric: [entries]}, "rounds": [round notes]}.

    Entries are ordered by round; ``extra_files`` (the ``--check``
    candidates) append after every checked-in round and are flagged
    ``candidate`` so the gate can tell history from the new claim."""
    files = sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json")),
                   key=_round_key)
    metrics, rounds = {}, []
    for path in files:
        records, note = load_bench_file(path)
        label = _round_label(path)
        if note is not None:
            rounds.append({"round": label, "note": note})
            continue
        rounds.append({"round": label, "records": len(records)})
        for rec in records:
            e = _entry(rec, label, os.path.basename(path))
            metrics.setdefault(e["metric"], []).append(e)
    for path in extra_files:
        records, note = load_bench_file(path)
        if note is not None:
            raise FileNotFoundError(
                f"--check {path}: {note} -- a candidate must parse")
        for rec in records:
            e = _entry(rec, "candidate", os.path.basename(path))
            e["candidate"] = True
            metrics.setdefault(e["metric"], []).append(e)
    return {"metrics": metrics, "rounds": rounds}


def gate(trajectory, tolerance=0.05, require_trusted=False):
    """Evaluate the regression gate; returns (regressions, notes).

    Per metric: the newest baseline-eligible entry is the claim under
    test; the BEST earlier baseline-eligible value is the bar.  For
    higher-is-better metrics (images/sec, tokens/sec, req/s speedups,
    wire-byte reduction ratios) best = max and a claim more than
    ``tolerance`` BELOW it regresses; for lower-is-better peak-bytes
    metrics (``metric_direction``) best = min and a claim more than
    ``tolerance`` ABOVE it regresses -- memory creep trips the gate
    exactly like an MFU drop.  With ``require_trusted``, a candidate
    whose trust class is not baseline-eligible fails outright -- CI
    for perf PRs that MUST ship a trusted number."""
    regressions, notes = [], []
    for metric, entries in sorted(trajectory["metrics"].items()):
        candidates = [e for e in entries if e.get("candidate")]
        under_test = candidates or entries[-1:]
        for cand in under_test:
            history = [e for e in entries
                       if e is not cand and not e.get("candidate")
                       and e["baseline_eligible"]]
            if cand["trust"] == "invalid:traced" \
                    and cand.get("candidate"):
                # unconditional: a --check candidate benched with
                # always-sample tracing is refused outright (every
                # request paid forced span flushes -- rerun the bench
                # with tracing at the default sample rate)
                regressions.append(
                    f"{metric}: candidate ({cand['file']}) was "
                    f"measured with always-sample tracing enabled -- "
                    f"rerun without BIGDL_TRACE_SAMPLE=1")
                continue
            if not cand["baseline_eligible"]:
                msg = (f"{metric}: newest record ({cand['round']}) is "
                       f"not baseline-eligible (trust {cand['trust']}"
                       + (", superseded" if cand["superseded"] else "")
                       + ") -- it can neither regress nor advance the "
                       "trajectory")
                if require_trusted and cand.get("candidate"):
                    regressions.append(msg)
                else:
                    notes.append(msg)
                continue
            if not history:
                notes.append(f"{metric}: first trusted record "
                             f"({cand['round']}, {cand['value']:g} "
                             f"{cand['unit'] or ''}) sets the baseline")
                continue
            if cand.get("direction") == "lower":
                best = min(history, key=lambda e: e["value"])
                ceiling = best["value"] * (1.0 + tolerance)
                if cand["value"] > ceiling:
                    regressions.append(
                        f"{metric}: {cand['round']} = {cand['value']:g} "
                        f"{cand['unit'] or ''} regresses the trusted "
                        f"baseline {best['value']:g} ({best['round']}) "
                        f"by {cand['value'] / best['value'] - 1:.1%} "
                        f"growth (> {tolerance:.0%} tolerance, "
                        f"lower-is-better)")
                else:
                    notes.append(
                        f"{metric}: {cand['round']} = {cand['value']:g} "
                        f"holds the trusted baseline {best['value']:g} "
                        f"({best['round']}, lower-is-better)")
                continue
            best = max(history, key=lambda e: e["value"])
            floor = best["value"] * (1.0 - tolerance)
            if cand["value"] < floor:
                regressions.append(
                    f"{metric}: {cand['round']} = {cand['value']:g} "
                    f"{cand['unit'] or ''} regresses the trusted "
                    f"baseline {best['value']:g} ({best['round']}) by "
                    f"{1 - cand['value'] / best['value']:.1%} "
                    f"(> {tolerance:.0%} tolerance)")
            else:
                notes.append(
                    f"{metric}: {cand['round']} = {cand['value']:g} "
                    f"holds the trusted baseline {best['value']:g} "
                    f"({best['round']})")
    if not any(e["baseline_eligible"]
               for es in trajectory["metrics"].values() for e in es):
        notes.append("trajectory has NO baseline-eligible record yet: "
                     "nothing trusted to gate against")
    return regressions, notes


def format_trajectory(trajectory, regressions, notes):
    """The obs_report-style "Trajectory" section (text form)."""
    out = ["== Trajectory =="]
    for r in trajectory["rounds"]:
        if "note" in r:
            out.append(f"  {r['round']:<14} -- {r['note']}")
    for metric, entries in sorted(trajectory["metrics"].items()):
        out.append(f"{metric}:")
        for e in entries:
            flags = []
            if e["superseded"]:
                flags.append("SUPERSEDED")
            if e.get("candidate"):
                flags.append("candidate")
            if e["baseline_eligible"]:
                flags.append("baseline-eligible")
            if e.get("direction") == "lower":
                flags.append("lower-is-better")
            v = "-" if e["value"] is None else f"{e['value']:g}"
            out.append(f"  {e['round']:<14} {v:>12} {e['unit'] or '':<10}"
                       f" trust={e['trust']:<22}"
                       + (" [" + ", ".join(flags) + "]" if flags else ""))
    for n in notes:
        out.append(f"note: {n}")
    for r in regressions:
        out.append(f"REGRESSION: {r}")
    out.append("gate: " + ("FAIL" if regressions else "PASS"))
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=REPO,
                    help="directory holding the BENCH_*.json history")
    ap.add_argument("--check", action="append", default=[],
                    metavar="FILE",
                    help="candidate record(s) to gate against the "
                         "history (repeatable); without it the newest "
                         "checked-in record is the claim under test")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="allowed fractional drop below the best "
                         "trusted baseline")
    ap.add_argument("--require-trusted", action="store_true",
                    help="fail when a --check candidate is not "
                         "baseline-eligible (untrusted/superseded)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)
    trajectory = build_trajectory(args.dir, extra_files=args.check)
    regressions, notes = gate(trajectory, tolerance=args.tolerance,
                              require_trusted=args.require_trusted)
    if args.format == "json":
        print(json.dumps({"trajectory": trajectory, "notes": notes,
                          "regressions": regressions,
                          "ok": not regressions}, indent=2))
    else:
        print(format_trajectory(trajectory, regressions, notes))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
