"""Perf regression gate: compare a bench artifact against a checked-in
baseline with per-metric tolerance bands.

``python -m orleans_tpu.perfgate`` loads ``PERF_BASELINE.json`` (the
committed contract — one entry per guarded metric: the dotted path into
the bench artifact, the baseline value, a fractional tolerance band and
a direction) and the freshest ``BENCH_r*.json`` in the working
directory, then renders a pass/fail verdict as one JSON line plus an
optional markdown table.  Exit code 0 = pass, 1 = regression, 2 = no
usable inputs.

Why a gate and not a dashboard: BENCH rounds r01→r05 carried at least
two silent regressions (a 20.5s collection stall, a 100x stream-plane
shortfall) that were visible in the artifacts for multiple rounds before
anyone compared numbers.  VERDICT r5 weak #8 names the pattern — "there
is no trend guard, so a regression would be invisible behind the note".
The gate makes round-over-round comparison a mechanical step
(``bench.py --workload profile --smoke`` runs it and embeds the
verdict in PROFILE_SMOKE.json).

Tolerance discipline: bands are wide (30-60%) because the pre-PR-1 chip
rig's run-to-run variance was real and measured — the gate exists to catch
order-of-magnitude cliffs and steady drifts, not 5% noise.  Direction
matters: an IMPROVEMENT never fails, in either direction's metric.

Artifact shapes accepted: the bare ``bench.py`` JSON, or the driver
wrapper ``{"parsed": {...}}`` (unwrapped automatically; a wrapper whose
``parsed`` is null — round r05's truncation — is reported as
unusable rather than silently passing).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

STATUS_PASS = "pass"
STATUS_FAIL = "fail"
STATUS_MISSING = "missing"

DIRECTION_HIGHER = "higher"   # regression when current < base * (1 - tol)
DIRECTION_LOWER = "lower"     # regression when current > base * (1 + tol)
# a truth FLAG (e.g. honored_strict): regression whenever current <
# baseline, tolerance IGNORED — an honored latency budget going
# unhonored is always a failure; unhonored→honored is an improvement
DIRECTION_FLAG = "flag"


def resolve_path(obj: Any, path: str,
                 allow_bool: bool = False) -> Optional[float]:
    """Walk a dotted path (``a.b.c``) through dicts; returns None when
    any hop is absent or the leaf is not a number.  ``allow_bool``
    (flag-direction metrics) maps True/False to 1.0/0.0 instead of
    rejecting them."""
    cur = obj
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    if isinstance(cur, bool):
        return (1.0 if cur else 0.0) if allow_bool else None
    if not isinstance(cur, (int, float)):
        return None
    return float(cur)


def unwrap_artifact(data: Any) -> Optional[Dict[str, Any]]:
    """Accept a bare bench artifact or the driver wrapper; None when the
    wrapper's parsed payload is null/absent (a truncated capture must
    read as 'unusable', never as 'no regressions').  The legacy opaque
    multichip wrapper ({n_devices, rc, ok, tail} with no metrics) reads
    as unusable too — only structured artifacts (a ``workload`` key or
    the bench headline keys) are comparable."""
    if not isinstance(data, dict):
        return None
    if "parsed" in data:
        parsed = data["parsed"]
        return parsed if isinstance(parsed, dict) else None
    # a bare artifact has the bench's headline keys (or, for the
    # multichip family, the structured tier's workload tag)
    return data if ("value" in data or "metric" in data
                    or "workload" in data) else None


#: artifact family → (round-file prefix, baseline metrics section,
#: fallback artifact written directly by bench.py)
FAMILIES: Dict[str, Tuple[str, str, Optional[str]]] = {
    "bench": ("BENCH", "metrics", None),
    "multichip": ("MULTICHIP", "multichip_metrics",
                  "MULTICHIP_BENCH.json"),
    "latency": ("LATENCY", "latency_metrics", "LATENCY_BENCH.json"),
    "attribution": ("ATTRIBUTION", "attribution_metrics",
                    "ATTRIBUTION_BENCH.json"),
    "streams": ("STREAMS", "streams_metrics", "STREAMS_BENCH.json"),
    "durability": ("DURABILITY", "durability_metrics",
                   "DURABILITY_BENCH.json"),
    "rpc": ("RPC", "rpc_metrics", "RPC_BENCH.json"),
    "rebalance": ("REBALANCE", "rebalance_metrics",
                  "REBALANCE_BENCH.json"),
    "timers": ("TIMERS", "timers_metrics", "TIMERS_BENCH.json"),
    "timeline": ("TIMELINE", "timeline_metrics", "TIMELINE_BENCH.json"),
}


def check_rig(baseline: Dict[str, Any],
              artifact: Dict[str, Any]) -> Dict[str, Any]:
    """Compare the artifact's ``rig`` header (bench.py _rig_header:
    toolchain versions + device identity) against the baseline's
    recorded rig.  A mismatch is a WARNING, never a failure — the bands
    still evaluate, but the verdict says the numbers were measured on
    different hardware/toolchains so the reader stops trusting small
    ratios (this repo's CPU-mesh multichip rounds are the cautionary
    tale).  Artifacts predating the rig header report 'unknown'."""
    base_rig = baseline.get("rig")
    art_rig = artifact.get("rig")
    if not isinstance(base_rig, dict) or not isinstance(art_rig, dict):
        return {"status": "unknown",
                "note": "rig header absent from "
                        + ("baseline and artifact"
                           if not isinstance(base_rig, dict)
                           and not isinstance(art_rig, dict)
                           else "baseline" if not isinstance(base_rig,
                                                             dict)
                           else "artifact")}
    mismatches = [
        {"field": k, "baseline": base_rig[k], "artifact": art_rig[k]}
        for k in sorted(set(base_rig) & set(art_rig))
        if k != "schema_version" and base_rig[k] != art_rig[k]]
    if mismatches:
        return {"status": "mismatch", "mismatches": mismatches,
                "warning": "artifact and baseline were measured on "
                           "differing rigs ("
                           + ", ".join(m["field"] for m in mismatches)
                           + ") — tolerance bands compare "
                             "apples to oranges"}
    return {"status": "match"}


def evaluate_metric(name: str, spec: Dict[str, Any],
                    artifact: Dict[str, Any]) -> Dict[str, Any]:
    base = float(spec["value"])
    tol = float(spec.get("tolerance", 0.3))
    direction = spec.get("direction", DIRECTION_HIGHER)
    current = resolve_path(artifact, spec["path"],
                           allow_bool=(direction == DIRECTION_FLAG))
    row: Dict[str, Any] = {
        "name": name, "path": spec["path"], "baseline": base,
        "current": current, "tolerance": tol, "direction": direction,
    }
    if current is None:
        row["status"] = STATUS_MISSING
        return row
    row["ratio"] = round(current / base, 4) if base else None
    if direction == DIRECTION_FLAG:
        # truth flag: tolerance NEVER widens this — a flag the baseline
        # holds must stay held (honored→unhonored always fails);
        # gaining a flag the baseline lacked passes
        row["bound"] = base
        row["status"] = STATUS_FAIL if current < base else STATUS_PASS
        return row
    if direction == DIRECTION_LOWER:
        bound = base * (1.0 + tol)
        row["bound"] = bound
        row["status"] = STATUS_FAIL if current > bound else STATUS_PASS
    else:
        bound = base * (1.0 - tol)
        row["bound"] = bound
        row["status"] = STATUS_FAIL if current < bound else STATUS_PASS
    return row


def evaluate(baseline: Dict[str, Any], artifact: Dict[str, Any],
             strict_missing: bool = False) -> Dict[str, Any]:
    """The verdict: per-metric rows + an overall status.  Missing
    metrics warn by default (auxiliary bench sections degrade to error
    entries by design — see bench._guard); ``strict_missing`` promotes
    them to failures for CI setups that want full coverage."""
    rows = [evaluate_metric(name, spec, artifact)
            for name, spec in baseline.get("metrics", {}).items()]
    if not rows:
        # a baseline that checks NOTHING must read as broken, never as
        # "pass" — a silently-unguarding gate is the exact failure mode
        # this module exists to prevent
        return {"status": "error",
                "error": "baseline declares no metrics (missing or "
                         "empty 'metrics' mapping)",
                "checked": 0, "passed": 0, "failed": 0, "missing": 0,
                "baseline_source": baseline.get("source", ""),
                "metrics": []}
    failed = [r for r in rows if r["status"] == STATUS_FAIL]
    missing = [r for r in rows if r["status"] == STATUS_MISSING]
    ok = not failed and not (strict_missing and missing)
    return {
        "status": STATUS_PASS if ok else STATUS_FAIL,
        "checked": len(rows),
        "passed": len([r for r in rows if r["status"] == STATUS_PASS]),
        "failed": len(failed),
        "missing": len(missing),
        "baseline_source": baseline.get("source", ""),
        "metrics": rows,
    }


def render_markdown(verdict: Dict[str, Any],
                    artifact_name: str = "") -> str:
    """Human-facing verdict table (written next to the JSON)."""
    icon = "✅ PASS" if verdict["status"] == STATUS_PASS else "❌ FAIL"
    lines = [
        f"# Perf gate: {icon}",
        "",
        f"Artifact: `{artifact_name or 'unknown'}` vs baseline "
        f"`{verdict.get('baseline_source', '')}` — "
        f"{verdict['passed']}/{verdict['checked']} within band, "
        f"{verdict['failed']} failed, {verdict['missing']} missing.",
        "",
        "| metric | baseline | current | ratio | band | status |",
        "|---|---|---|---|---|---|",
    ]

    def fmt(v: Optional[float]) -> str:
        if v is None:
            return "—"
        if abs(v) >= 1000:
            return f"{v:,.0f}"
        return f"{v:.4g}"

    for r in verdict["metrics"]:
        mark_dir = {DIRECTION_LOWER: "≤", DIRECTION_FLAG: "="} \
            .get(r["direction"], "≥")
        band = f"{mark_dir} {fmt(r.get('bound'))}"
        mark = {STATUS_PASS: "pass", STATUS_FAIL: "**FAIL**",
                STATUS_MISSING: "missing"}[r["status"]]
        lines.append(
            f"| {r['name']} | {fmt(r['baseline'])} | {fmt(r['current'])} "
            f"| {fmt(r.get('ratio'))} | {band} | {mark} |")
    rig = verdict.get("rig_check", {})
    if rig.get("status") == "mismatch":
        lines += ["", f"⚠️ RIG MISMATCH: {rig['warning']}"]
    lines.append("")
    return "\n".join(lines)


def newest_bench_artifact(directory: str = ".", family: str = "bench"
                          ) -> Optional[Tuple[str, Dict]]:
    """The freshest usable artifact of ``family`` by round number
    (unparseable/opaque rounds — e.g. the truncated round r05, or the
    legacy {n_devices, rc, ok} multichip wrappers — are skipped with a
    note to stderr, not silently treated as regression-free).  Families
    with a bench-written fallback artifact (MULTICHIP_BENCH.json) use it
    when no structured driver round exists."""
    prefix, _section, fallback = FAMILIES[family]
    rounds: List[Tuple[int, str]] = []
    for path in glob.glob(os.path.join(directory, f"{prefix}_r*.json")):
        m = re.search(rf"{prefix}_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    candidates = [path for _, path in sorted(rounds, reverse=True)]
    if fallback is not None:
        fb = os.path.join(directory, fallback)
        if os.path.exists(fb):
            candidates.append(fb)
    for path in candidates:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        artifact = unwrap_artifact(data)
        if artifact is not None:
            return path, artifact
        print(f"perfgate: skipping {path}: no parseable payload",
              file=sys.stderr)
    return None


def run_gate(baseline_path: str, artifact: Optional[Dict[str, Any]] = None,
             artifact_name: str = "",
             strict_missing: bool = False,
             family: str = "bench") -> Dict[str, Any]:
    """Library entry point (bench.py embeds this in the profile and
    multichip tiers).  ``family`` selects the artifact glob and the
    baseline metrics section (FAMILIES)."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    prefix, section, _fb = FAMILIES[family]
    if section != "metrics":
        baseline = {**baseline, "metrics": baseline.get(section, {})}
    if artifact is None:
        found = newest_bench_artifact(
            os.path.dirname(baseline_path) or ".", family=family)
        if found is None:
            return {"status": "error",
                    "error": f"no usable {prefix} artifact found"}
        artifact_name, artifact = found[0], found[1]
    verdict = evaluate(baseline, artifact, strict_missing=strict_missing)
    verdict["artifact"] = artifact_name
    verdict["family"] = family
    verdict["rig_check"] = check_rig(baseline, artifact)
    return verdict


def run_all_families(baseline_path: str,
                     strict_missing: bool = False) -> Dict[str, Any]:
    """The one-CI-gate entrypoint (``--all-families``): evaluate every
    artifact family against its baseline section in one invocation.
    Combined status is the worst family's — any fail beats any error
    beats pass — so one exit code guards the whole perf surface; a
    family whose artifact or baseline section is missing reads as an
    error entry, never as silently skipped."""
    families: Dict[str, Any] = {}
    for family in sorted(FAMILIES):
        try:
            families[family] = run_gate(baseline_path,
                                        strict_missing=strict_missing,
                                        family=family)
        except (OSError, json.JSONDecodeError, KeyError, TypeError,
                ValueError) as exc:
            families[family] = {"status": "error",
                                "error": f"{type(exc).__name__}: {exc}"}
    statuses = [v.get("status") for v in families.values()]
    combined = (STATUS_FAIL if STATUS_FAIL in statuses
                else "error" if "error" in statuses else STATUS_PASS)
    rig_warnings = {
        f: v["rig_check"]["warning"] for f, v in families.items()
        if v.get("rig_check", {}).get("status") == "mismatch"}
    out: Dict[str, Any] = {
        "status": combined,
        "families": families,
        "checked": sum(v.get("checked", 0) for v in families.values()),
        "failed": sum(v.get("failed", 0) for v in families.values()),
    }
    if rig_warnings:
        out["rig_warnings"] = rig_warnings
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m orleans_tpu.perfgate",
        description="compare a bench artifact against PERF_BASELINE.json "
                    "with per-metric tolerance bands")
    parser.add_argument("--baseline", default="PERF_BASELINE.json")
    parser.add_argument("--artifact", default=None,
                        help="bench artifact JSON (default: the freshest "
                             "usable BENCH_r*.json beside the baseline)")
    parser.add_argument("--markdown", default=None, metavar="PATH",
                        help="also write the verdict as a markdown table")
    parser.add_argument("--strict-missing", action="store_true",
                        help="treat metrics absent from the artifact as "
                             "failures instead of warnings")
    parser.add_argument("--family", choices=sorted(FAMILIES),
                        default="bench",
                        help="artifact family: 'bench' compares "
                             "BENCH_r*.json against the baseline's "
                             "'metrics'; 'multichip' compares the "
                             "structured multichip artifacts "
                             "(MULTICHIP_r*.json / MULTICHIP_BENCH"
                             ".json) against 'multichip_metrics'; "
                             "'latency' compares LATENCY_r*.json / "
                             "LATENCY_BENCH.json against "
                             "'latency_metrics' (honored flags use "
                             "direction 'flag': honored→unhonored "
                             "always fails); 'attribution' compares "
                             "ATTRIBUTION_r*.json / ATTRIBUTION_BENCH"
                             ".json against 'attribution_metrics'; "
                             "'streams' compares STREAMS_r*.json / "
                             "STREAMS_BENCH.json against "
                             "'streams_metrics' (exactness flags use "
                             "direction 'flag'); 'rpc' compares "
                             "RPC_r*.json / RPC_BENCH.json against "
                             "'rpc_metrics'; 'timers' compares "
                             "TIMERS_r*.json / TIMERS_BENCH.json "
                             "against 'timers_metrics' (sample "
                             "exactness oracles use direction 'flag', "
                             "the <5% armed-wheel overhead bar uses "
                             "direction 'lower')")
    parser.add_argument("--all-families", action="store_true",
                        help="evaluate EVERY family in one invocation "
                             "(the one CI gate entrypoint): combined "
                             "JSON verdict, single exit code — any "
                             "family failing fails the gate, any "
                             "unusable family is exit 2")
    args = parser.parse_args(argv)

    if args.all_families:
        if args.artifact:
            print(json.dumps({"status": "error",
                              "error": "--all-families locates each "
                                       "family's artifact itself; "
                                       "--artifact conflicts with it"}))
            return 2
        if not os.path.exists(args.baseline):
            print(json.dumps({"status": "error",
                              "error": f"baseline {args.baseline} "
                                       "not found"}))
            return 2
        combined = run_all_families(args.baseline,
                                    strict_missing=args.strict_missing)
        for fam, warning in combined.get("rig_warnings", {}).items():
            print(f"perfgate: [{fam}] {warning}", file=sys.stderr)
        if args.markdown:
            md = "\n".join(
                render_markdown(v, v.get("artifact", ""))
                for v in combined["families"].values()
                if v.get("metrics") is not None)
            with open(args.markdown, "w") as f:
                f.write(md + "\n")
        print(json.dumps(combined))
        return {STATUS_PASS: 0, STATUS_FAIL: 1}.get(combined["status"], 2)

    if not os.path.exists(args.baseline):
        print(json.dumps({"status": "error",
                          "error": f"baseline {args.baseline} not found"}))
        return 2
    artifact = None
    artifact_name = ""
    if args.artifact:
        try:
            with open(args.artifact) as f:
                artifact = unwrap_artifact(json.load(f))
        except (OSError, json.JSONDecodeError) as exc:
            print(json.dumps({"status": "error",
                              "error": f"artifact: {exc}"}))
            return 2
        if artifact is None:
            print(json.dumps({"status": "error",
                              "error": f"artifact {args.artifact} has no "
                                       "parseable bench payload"}))
            return 2
        artifact_name = args.artifact

    try:
        verdict = run_gate(args.baseline, artifact, artifact_name,
                           strict_missing=args.strict_missing,
                           family=args.family)
    except (json.JSONDecodeError, KeyError, TypeError, ValueError) as exc:
        # a malformed baseline is a usage error (exit 2 + JSON), never a
        # raw traceback — the documented CLI contract
        print(json.dumps({"status": "error",
                          "error": f"baseline: {type(exc).__name__}: "
                                   f"{exc}"}))
        return 2
    if verdict.get("status") == "error":
        print(json.dumps(verdict))
        return 2
    rig = verdict.get("rig_check", {})
    if rig.get("status") == "mismatch":
        print(f"perfgate: {rig['warning']}", file=sys.stderr)
    md = render_markdown(verdict, verdict.get("artifact", artifact_name))
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(md + "\n")
    print(json.dumps(verdict))
    return 0 if verdict["status"] == STATUS_PASS else 1


if __name__ == "__main__":
    sys.exit(main())
