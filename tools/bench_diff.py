#!/usr/bin/env python
"""Regression gate between two bench-record JSON files (ISSUE 16
satellite).

A bench record tracks one headline metric per round plus a
`parsed` payload of secondary numbers (p50/p99 latency, MFU, goodput,
shed fraction, bucket hits...). Nothing gated those numbers: a round
could regress images/sec or p99 and the only trace would be a human
eyeballing two JSON files. This tool is the gate:

    python tools/bench_diff.py old.json new.json
    python tools/bench_diff.py old.json new.json --threshold 0.10
    python tools/bench_diff.py old.json new.json --json
    python tools/bench_diff.py --history BENCH_HISTORY.jsonl

`--history` (ISSUE 17 satellite) gates the standing ledger bench.py
appends to instead of two hand-picked files: entries are grouped by
(mode, family) plus precision variant (amp_level / quant, so an O3 or
int8 line never gates against its f32 sibling), and within each group
the NEWEST entry is compared
against the per-key rolling MEDIAN of all prior entries with the same
direction-aware thresholds. Groups with fewer than two entries are
skipped (nothing to compare against).

It walks both `parsed` dicts (recursing into sub-dicts like
`overload`/`normal` phases), classifies each shared numeric key by
direction — higher-better (value, qps, *fraction that measures goodput,
MFU, hit counts) vs lower-better (latencies, shed/miss/eviction rates,
seconds) — and flags any metric whose relative change exceeds the
threshold in the losing direction. Exit status: 0 clean, 1 regressions
found, 2 usage/parse errors. Keys present in only one file are reported
as informational drift, not failures (benchmarks grow fields).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

# direction vocabulary: a key matches the first rule whose substring it
# contains (checked in order) — explicit names first, suffix families
# after. "bucket_hits" style count dicts are compared per-key as
# higher-better (a bucket losing all its traffic is a distribution
# shift worth seeing).
LOWER_BETTER_MARKERS = (
    "p50_ms", "p99_ms", "latency", "_seconds", "seconds_", "wall_s",
    "shed_fraction", "miss", "eviction", "stall", "skew", "dropped",
    "timeout", "error", "exposed", "overhead", "fallback",
)
HIGHER_BETTER_MARKERS = (
    "value", "qps", "images_per_sec", "mfu", "tflops", "goodput",
    "hit", "coverage", "duty_cycle", "busbw", "overlap", "vs_baseline",
)


def direction(key: str) -> Optional[str]:
    """'higher' | 'lower' | None (uncompared) for one metric key."""
    k = key.lower()
    for marker in LOWER_BETTER_MARKERS:
        if marker in k:
            return "lower"
    for marker in HIGHER_BETTER_MARKERS:
        if marker in k:
            return "higher"
    return None


def _flatten(d: Dict, prefix: str = "") -> Dict[str, float]:
    """parsed dict -> {dotted.key: float} over numeric leaves."""
    out: Dict[str, float] = {}
    for k, v in d.items():
        path = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten(v, path))
        elif isinstance(v, bool):
            continue
        elif isinstance(v, (int, float)) and v is not None:
            out[path] = float(v)
    return out


def diff(old: Dict, new: Dict, threshold: float = 0.05) \
        -> Tuple[List[Dict], List[Dict], List[str]]:
    """-> (regressions, improvements, drift). Each entry: {key, old,
    new, change} with change as signed relative delta in the metric's
    natural direction (positive = better)."""
    old_flat = _flatten(old.get("parsed") or {})
    new_flat = _flatten(new.get("parsed") or {})
    regressions, improvements = [], []
    drift = sorted(set(old_flat) ^ set(new_flat))
    for key in sorted(set(old_flat) & set(new_flat)):
        sense = direction(key)
        if sense is None:
            continue
        a, b = old_flat[key], new_flat[key]
        if a == b:
            continue
        base = max(abs(a), 1e-12)
        rel = (b - a) / base
        gain = rel if sense == "higher" else -rel
        entry = {"key": key, "old": a, "new": b,
                 "direction": sense, "change": gain}
        if gain < -threshold:
            regressions.append(entry)
        elif gain > threshold:
            improvements.append(entry)
    regressions.sort(key=lambda e: e["change"])
    improvements.sort(key=lambda e: -e["change"])
    return regressions, improvements, drift


# ledger metadata stamped by bench._append_history (or non-numeric):
# excluded from comparison so a sha change is not a "regression"
_HISTORY_META_KEYS = {"ts", "git_sha", "mode", "family", "metric",
                      "unit", "errors", "amp_level", "quant"}


def _median(vals: List[float]) -> float:
    vals = sorted(vals)
    k = len(vals) // 2
    return vals[k] if len(vals) % 2 else 0.5 * (vals[k - 1] + vals[k])


def _variant(e: Dict) -> str:
    """Precision-variant tag for grouping: an O3/int8 line is a different
    configuration, not a regression of the O2/f32 line it rides next to
    in the ledger (XLA:CPU int8 matmuls are *slower* than bf16, so mixing
    them in one group would flag every quantized run)."""
    tags = [str(t) for t in (e.get("amp_level"), e.get("quant")) if t]
    return "+".join(tags)


def history_diff(entries: List[Dict], threshold: float = 0.05) \
        -> Tuple[List[Dict], List[Tuple[str, str, int]]]:
    """-> (regressions, groups). Newest entry per (mode, family,
    precision-variant) vs the per-key median of that group's prior
    entries, direction-aware. Each regression entry adds 'group';
    `groups` lists (mode, family, n) for every group seen (n < 2 means
    skipped)."""
    by_group: Dict[Tuple[str, str], List[Dict]] = {}
    for e in entries:
        mode = str(e.get("mode", "?"))
        tag = _variant(e)
        if tag:
            mode = f"{mode}[{tag}]"
        key = (mode, str(e.get("family", "?")))
        by_group.setdefault(key, []).append(e)

    regressions: List[Dict] = []
    groups: List[Tuple[str, str, int]] = []
    for (mode, family), group in sorted(by_group.items()):
        groups.append((mode, family, len(group)))
        if len(group) < 2:
            continue
        newest = _flatten({k: v for k, v in group[-1].items()
                           if k not in _HISTORY_META_KEYS})
        prior_flat = [_flatten({k: v for k, v in e.items()
                                if k not in _HISTORY_META_KEYS})
                      for e in group[:-1]]
        for key in sorted(newest):
            sense = direction(key)
            if sense is None:
                continue
            priors = [p[key] for p in prior_flat if key in p]
            if not priors:
                continue
            med = _median(priors)
            b = newest[key]
            if med == b:
                continue
            base = max(abs(med), 1e-12)
            rel = (b - med) / base
            gain = rel if sense == "higher" else -rel
            if gain < -threshold:
                regressions.append({
                    "group": f"{mode}/{family}", "key": key,
                    "old": med, "new": b, "direction": sense,
                    "change": gain, "n_prior": len(priors)})
    regressions.sort(key=lambda e: e["change"])
    return regressions, groups


def _main_history(args) -> int:
    entries: List[Dict] = []
    try:
        with open(args.history) as f:
            for ln in f:
                ln = ln.strip()
                if ln:
                    entries.append(json.loads(ln))
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_diff: cannot read {args.history}: {e}",
              file=sys.stderr)
        return 2
    regressions, groups = history_diff(entries,
                                       threshold=args.threshold)
    if args.json:
        print(json.dumps({
            "history": args.history, "threshold": args.threshold,
            "groups": [{"mode": m, "family": f, "entries": n}
                       for m, f, n in groups],
            "regressions": regressions}, sort_keys=True))
    else:
        for e in regressions:
            print(f"REGRESSION {e['group']} {e['key']}: "
                  f"median {e['old']:g} -> {e['new']:g} "
                  f"({e['change']:+.1%}, {e['direction']}-is-better, "
                  f"n={e['n_prior']})")
        compared = sum(1 for _, _, n in groups if n >= 2)
        verdict = (f"{len(regressions)} regression"
                   f"{'' if len(regressions) == 1 else 's'} beyond "
                   f"{args.threshold:.0%}" if regressions
                   else f"bench history ok ({compared} group"
                        f"{'' if compared == 1 else 's'} compared)")
        print(verdict)
    return 1 if regressions else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare two BENCH_*.json files (or gate the "
                    "BENCH_HISTORY.jsonl ledger with --history); "
                    "nonzero exit on regression beyond --threshold")
    ap.add_argument("old", nargs="?", help="baseline BENCH json")
    ap.add_argument("new", nargs="?", help="candidate BENCH json")
    ap.add_argument("--history", default=None,
                    help="BENCH_HISTORY.jsonl ledger: compare the newest "
                         "entry per (mode, family, precision variant) "
                         "against the median of prior entries")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression tolerance (default 0.05 "
                         "= 5%%)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable single-line JSON output")
    args = ap.parse_args(argv)

    if args.history is not None:
        return _main_history(args)
    if not args.old or not args.new:
        print("bench_diff: need OLD and NEW files (or --history LEDGER)",
              file=sys.stderr)
        return 2

    payloads = []
    for path in (args.old, args.new):
        try:
            with open(path) as f:
                payloads.append(json.load(f))
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_diff: cannot read {path}: {e}",
                  file=sys.stderr)
            return 2
    regressions, improvements, drift = diff(
        payloads[0], payloads[1], threshold=args.threshold)

    if args.json:
        print(json.dumps({
            "old": args.old, "new": args.new,
            "threshold": args.threshold, "regressions": regressions,
            "improvements": improvements, "drift": drift},
            sort_keys=True))
    else:
        for e in regressions:
            print(f"REGRESSION {e['key']}: {e['old']:g} -> {e['new']:g} "
                  f"({e['change']:+.1%}, {e['direction']}-is-better)")
        for e in improvements:
            print(f"improved   {e['key']}: {e['old']:g} -> {e['new']:g} "
                  f"({e['change']:+.1%})")
        for key in drift:
            print(f"drift      {key}: present in only one file")
        verdict = (f"{len(regressions)} regression"
                   f"{'' if len(regressions) == 1 else 's'} beyond "
                   f"{args.threshold:.0%}"
                   if regressions else "bench diff ok")
        print(verdict)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
