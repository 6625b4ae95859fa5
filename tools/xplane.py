"""CLI over paddle_tpu.xplane: per-op time aggregation of jax.profiler
xplane traces (the tensorboard profile plugin in this image can't load
them — TF version skew — so this decodes the wire format directly).

Usage: python tools/xplane.py <trace_dir_or_file> [top_n]
       python tools/xplane.py --timeline <trace_dir_or_file> [max_events]
       python tools/xplane.py --collectives <trace_dir> [top_n]

The default view sums the device's own timeline (`xplane.device_steps`:
the `XLA Ops` line, never the derived lines) per core by instruction and
by kind; --timeline prints each line's events in execution order
(XLine.timestamp_ns anchor + XEvent.offset_ps), the raw view behind the
profiler's step-time waterfall; --collectives prints the collective rows
of the trace's account (`xplane.step_account`, joined to the account an
earlier reader saved beside the trace, if any) — kind, mesh axis, total
and exposed ms, bus bandwidth — summed per kind at the end: the stdlib
view behind `python -m paddle_tpu fleet`.
"""

from __future__ import annotations

import importlib.util
import os
import sys

# load paddle_tpu/xplane.py directly by path: it is pure stdlib, and going
# through the package __init__ would drag in jax/the framework — this CLI
# must keep working in the stripped TF-skew environments it exists for
_xp_path = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "xplane.py")
_spec = importlib.util.spec_from_file_location("_xplane_standalone",
                                               _xp_path)
_xplane = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_xplane)
category = _xplane.category


def timeline(target, limit):
    if os.path.isdir(target):
        records = _xplane.timeline_dir(target)
    else:
        records = [{"plane": pname, "line": line["name"],
                    "timestamp_ns": line["timestamp_ns"],
                    "events": line["events"]}
                   for pname, lines in _xplane.plane_events(target).items()
                   for line in lines]
    for rec in records:
        if not rec["events"]:
            continue
        print(f"-- {rec['plane']} / '{rec['line']}' "
              f"@ {rec['timestamp_ns']} ns")
        evs = sorted(rec["events"], key=lambda e: e[1])[:limit]
        base = evs[0][1]
        for name, off, dur in evs:
            print(f"   +{(off - base) / 1e6:12.3f} us  "
                  f"{dur / 1e6:10.3f} us  {name[:90]}")


def collectives(target, limit):
    account = _xplane.step_account(target)
    rows = {}
    for step in (account or {}).get("steps", ()):
        for r in step["rows"]:
            if r["kind"]:
                acc = rows.setdefault(r["name"], dict(r, ms=0.0,
                                                      exposed_ms=0.0))
                acc["ms"] += r["ms"]
                acc["exposed_ms"] += r["exposed_ms"]
    if not rows:
        print("(no collective events)")
        return
    by_kind = {}
    print(f"{'total ms':>10s} {'exposed ms':>11s} {'busbw GB/s':>11s}  "
          f"kind / axis / event")
    for name, rec in sorted(rows.items(), key=lambda kv: -kv[1]["ms"])[:limit]:
        bus = ("%11.2f" % rec["busbw_gbps"]) if rec["busbw_gbps"] else \
            "          -"
        print(f"{rec['ms']:10.3f} {rec['exposed_ms']:11.3f} {bus}  "
              f"{rec['kind']:18s} {rec['axis'] or '-':8s} {name[:70]}")
    for rec in rows.values():
        agg = by_kind.setdefault(rec["kind"], [0.0, 0.0])
        agg[0] += rec["ms"]
        agg[1] += rec["exposed_ms"]
    for kind, (tot, exp) in sorted(by_kind.items(), key=lambda kv: -kv[1][0]):
        print(f"[kind] {kind:18s} {tot:10.3f} ms total, "
              f"{exp:.3f} ms exposed")


def main():
    args = sys.argv[1:]
    want_timeline = "--timeline" in args
    if want_timeline:
        args.remove("--timeline")
    want_collectives = "--collectives" in args
    if want_collectives:
        args.remove("--collectives")
    target = args[0] if args else "."
    top = int(args[1]) if len(args) > 1 else 30
    if want_collectives:
        collectives(target, top)
        return
    if want_timeline:
        timeline(target, top)
        return
    per_device = {}
    for step in _xplane.device_steps(target):
        agg = per_device.setdefault(step["device"], {})
        for name, ps in _xplane._self_ps(step["events"]).items():
            agg[name] = agg.get(name, 0) + ps
    for pname, agg in per_device.items():
        total = sum(agg.values())
        if not total:
            continue
        print(f"-- '{pname}': sum {total / 1e9:.2f} ms")
        cats = {}
        for name, ps in agg.items():
            c = category(name)
            cats[c] = cats.get(c, 0) + ps
        for c, ps in sorted(cats.items(), key=lambda kv: -kv[1])[:15]:
            print(f"   [cat] {ps / 1e9:10.2f} ms  {c}")
        for name, ps in sorted(agg.items(), key=lambda kv: -kv[1])[:top]:
            print(f"   {ps / 1e9:10.2f} ms  {name[:110]}")


if __name__ == "__main__":
    main()
