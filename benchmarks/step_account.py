#!/usr/bin/env python3
"""From the traced steps' profiler trace to the account of a step by
instruction: what each HLO instruction the device ran is (kind, the
program op instance it was lowered from, FLOPs, bytes, a collective's
mesh axis), how long it took, and the least the chip could have taken.

    python3 benchmarks/step_account.py <trace_dir> [top_n]

prints the tables PERF.md section 5 is written from: the instructions
that took the most time, the same grouped by program op instance, by
what does the work inside (`heavy`) and, for XLA's products, by shape
(105 backward convs are 40 shapes), and the collectives by mesh axis and
kind with their bytes and bus bandwidth. trace_reduce.py answers "how
busy was the device", program_trace.py "which program op and which host
span"; this file joins the same `XLA Ops` events to the account the
executor keeps of each compiled step (`Executor.step_account`, built from
the one parse of the compiled text, `paddle_tpu.xplane.hlo_instructions`)
through the program's own reader, `paddle_tpu.xplane.step_account`. In
the process that traced, the account is the executor's; that first join
saves it beside the trace (`step_account.json`), so this prints later
from the directory alone.

A row's floor is max(FLOPs / peak, bytes / HBM bandwidth) of the chip
(`paddle_tpu/chip.py`); its floor share, floor over time, says how near
the instruction ran to the roofline. An async `-start` / `-done` half has
no floor of its own (the transfer runs under the instructions between),
and a Mosaic call's FLOPs are its kernel family's to give
(`benchmarks/rooflines.py`), so its floor here is its bytes alone and
the readers leave it out. A parent program keeps no account: every
reader returns None there.
"""

import functools
import os
import re
import statistics
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

PRODUCTS = ("dot", "convolution")
# the events `exposed_collective_ms.train` counts, by instruction name
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


@functools.lru_cache(maxsize=4)
def _account(trace_dir, _stamp):
    from paddle_tpu import xplane

    join = getattr(xplane, "step_account", None)
    return join(trace_dir) if join else None


def account_of_dir(trace_dir):
    """`xplane.step_account(trace_dir)`, once a trace; None where the
    program has no such reader or the trace no operation."""
    from paddle_tpu import xplane

    files = getattr(xplane, "_xplane_files", lambda d: [])(trace_dir)
    if not files:
        return None
    return _account(trace_dir, max(os.path.getmtime(p) for p in files))


def of_evidence(ev):
    """The account of the traced steps of the run `ev` is the evidence of
    (run.TRACE_DIR/<cell>), or None: not traced, no file, a program that
    keeps no account (nothing joined), or a trace with no device plane
    (the CPU's: the program's own report reads its host threads, a
    `device_trace` metric does not)."""
    from benchmarks import run

    account = account_of_dir(os.path.join(run.TRACE_DIR, ev["cell"]["name"]))
    if account is None or not account.get("joined"):
        return None
    if any(step.get("host") for step in account["steps"]):
        return None     # a CPU trace: host threads, no device's timeline
    return account


def steps_of(account):
    from paddle_tpu import xplane

    return xplane.main_steps(account)


def is_product(row):
    """XLA's own products: a dot or a convolution, alone or inside a
    fusion (a v5e compiles a dot to a convolution); Mosaic calls aside."""
    return row["joined"] and row["heavy"] in PRODUCTS


def is_copy(row):
    """Data moved and nothing computed: copy, copy-start / -done,
    transpose, slices, and fusions with no arithmetic inside."""
    return row["joined"] and row["heavy"] == "copy"


def is_membound(row):
    """No product inside, not a copy, not a collective, not a Mosaic
    call, and the floor is the bytes (Adam, residual adds, norms, the
    loss)."""
    if not row["joined"] or row["heavy"] in PRODUCTS + ("copy", "collective",
                                                        "control"):
        return False
    if row["flops"] is None or row["kind"]:
        return False
    return row["bound"] == "bytes"


def floor_share_pct(ev, select):
    """100 x sum of floors over sum of device time of the rows `select`
    picks, median over the traced steps; None without an account, without
    the chip's peaks, or where no step holds such a row."""
    account = of_evidence(ev)
    if account is None or not account["peak_flops"]:
        return None
    shares = []
    for step in steps_of(account):
        rows = [r for r in step["rows"]
                if r["floor_ms"] is not None and select(r)]
        taken = sum(r["ms"] for r in rows)
        if taken > 0:
            shares.append(100.0 * sum(r["floor_ms"] for r in rows) / taken)
    return statistics.median(shares) if shares else None


def time_pct(ev, select):
    """100 x device time of the rows `select` picks over busy time,
    median over the traced steps; None without an account."""
    account = of_evidence(ev)
    if account is None:
        return None
    shares = [100.0 * sum(r["ms"] for r in step["rows"] if select(r))
              / step["busy_ms"] for step in steps_of(account)
              if step["busy_ms"] > 0]
    return statistics.median(shares) if shares else None


def collective_ms_by_axis(ev):
    """{mesh axis: ms a traced step and chip} of the events
    `exposed_collective_ms.train` counts (instructions named after a
    collective, `-start` / `-done` and fusions included), keyed by the
    axis of each instruction's replica groups: 'tp', 'fsdp', a name with
    '+' where a group spans both axes, None where the account gives no
    axis. The values sum to that metric. None without an account or a
    collective."""
    account = of_evidence(ev)
    if account is None:
        return None
    devices = {step["device"] for step in account["steps"]}
    totals = {}
    for step in account["steps"]:
        for r in step["rows"]:
            if COLLECTIVE.search(r["name"]):
                totals[r["axis"]] = totals.get(r["axis"], 0.0) + r["ms"]
    if not totals:
        return None
    per = len(devices) * ev["cell"]["trace_steps"]
    return {axis: ms / per for axis, ms in totals.items()}


def axis_ms(ev, axis):
    by_axis = collective_ms_by_axis(ev)
    if by_axis is None or axis not in by_axis:
        return None
    return by_axis[axis]


# --- the tables --------------------------------------------------------------

def _mean_rows(steps, key):
    """{key(row): summed fields} a step (mean over the steps)."""
    groups = {}
    n = len(steps)
    for step in steps:
        for r in step["rows"]:
            g = groups.setdefault(key(r), {
                "ms": 0.0, "count": 0, "flops": 0.0, "bytes": 0.0,
                "floor_ms": 0.0, "payload": 0.0, "rows": 0, "sample": r})
            g["ms"] += r["ms"] / n
            g["count"] += r["count"] / n
            g["flops"] += (r["flops"] or 0.0) * r["count"] / n
            g["bytes"] += (r["bytes"] or 0) * r["count"] / n
            g["floor_ms"] += (r["floor_ms"] or 0.0) / n
            g["payload"] += (r["payload"] or 0) * r["count"] / n
            g["rows"] += 1
            if r["ms"] > g["sample"]["ms"]:
                g["sample"] = r         # the group goes by its longest row
    return groups


def _where(r):
    op = r["op"] or "-"
    if r["at"] is not None:
        op += "@%d" % r["at"]
    if r["scope"]:
        op = "%s:%s" % (r["scope"], op)
    return "%s/%s" % (r["role"][:3], op)


def _line(name, g, busy, extra=""):
    tflops = g["flops"] / (g["ms"] / 1e3) / 1e12 if g["ms"] else 0.0
    share = 100.0 * g["floor_ms"] / g["ms"] if g["ms"] else 0.0
    return "%-44s %9.3f %5.1f%% %6.0f %10.2f %10.1f %8.3f %6.1f%% %7.1f %s" % (
        name[:44], g["ms"], 100.0 * g["ms"] / busy, g["count"],
        g["flops"] / 1e9, g["bytes"] / 1e6, g["floor_ms"], share, tflops,
        extra)


HEAD = "%-44s %9s %6s %6s %10s %10s %8s %7s %7s %s" % (
    "", "ms", "busy", "runs", "GFLOP", "MB", "floor ms", "share", "TFLOP/s",
    "")


def print_tables(account, top=40):
    steps = steps_of(account)
    if not steps:
        print("no step in the trace")
        return
    devices = sorted({s["device"] for s in steps})
    busy = statistics.mean(s["busy_ms"] for s in steps)
    every = _mean_rows(steps, lambda r: 0)[0]
    print("device %s, %d chips, %d traced steps a chip; busy %.3f ms a "
          "step (mean), window %.3f; joined to an account: %.2f%% of busy "
          "time" % (account["device_kind"], len(devices),
                    len(steps) // len(devices), busy,
                    statistics.mean(s["window_ms"] for s in steps),
                    100.0 * account["joined"]))
    print("a step: %.2f GFLOP executed (Mosaic calls aside), %.1f MB "
          "moved, sum of floors %.3f ms = %.1f%% of busy" % (
              every["flops"] / 1e9, every["bytes"] / 1e6, every["floor_ms"],
              100.0 * every["floor_ms"] / busy))

    print("\nby what does the work (heavy), a step\n" + HEAD)
    groups = _mean_rows(steps, lambda r: r["heavy"])
    for heavy, g in sorted(groups.items(), key=lambda kv: -kv[1]["ms"]):
        print(_line(heavy, g, busy))

    print("\nby program op instance (role/scope:op@position), a step, top "
          "%d\n" % top + HEAD)
    groups = _mean_rows(steps, _where)
    for name, g in sorted(groups.items(), key=lambda kv: -kv[1]["ms"])[:top]:
        print(_line(name, g, busy, g["sample"]["heavy"]))

    shapes = _mean_rows(
        [dict(s, rows=[r for r in s["rows"] if r["heavy"] in PRODUCTS])
         for s in steps],
        lambda r: "%s/%s %s" % (r["role"][:3], r["op"] or "-", r["detail"]))
    if shapes:
        print("\nXLA's products by shape (role/op, operands, window), a "
              "step: every instance\n" + HEAD.replace("  runs", "    of"))
        for name, g in sorted(shapes.items(), key=lambda kv: -kv[1]["ms"]):
            print(_line(name.split(" ", 1)[0], g, busy,
                        name.split(" ", 1)[1]))

    print("\nby instruction, a step, top %d\n" % top + HEAD)
    groups = _mean_rows(steps, lambda r: r["name"])
    for name, g in sorted(groups.items(), key=lambda kv: -kv[1]["ms"])[:top]:
        r = g["sample"]
        print(_line("%s [%s]" % (name, r["heavy"]), g, busy,
                    "%s %s" % (_where(r), r["detail"] or r["shape"][:60])))

    colls = _mean_rows([dict(s, rows=[r for r in s["rows"] if r["kind"]])
                        for s in steps],
                       lambda r: (r["axis"], r["kind"], r["heavy"]))
    if colls:
        print("\ncollectives by mesh axis and kind, a step and chip")
        print("%-10s %-20s %-12s %6s %9s %10s %11s" % (
            "axis", "kind", "heavy", "runs", "ms", "MB", "busbw GB/s"))
        named = 0.0
        from paddle_tpu import xplane
        for (axis, kind, heavy), g in sorted(
                colls.items(), key=lambda kv: -kv[1]["ms"]):
            n = g["sample"]["group_size"] or 1
            bus = g["payload"] * xplane.busbw_factor(kind, n) \
                / (g["ms"] / 1e3) / 1e9 if g["ms"] else 0.0
            print("%-10s %-20s %-12s %6.0f %9.3f %10.2f %11.2f" % (
                axis or "-", kind, heavy, g["count"], g["ms"],
                g["payload"] / 1e6, bus))
        by_axis = {}
        for step in steps:
            for r in step["rows"]:
                if COLLECTIVE.search(r["name"]):
                    by_axis[r["axis"]] = by_axis.get(r["axis"], 0.0) \
                        + r["ms"] / len(steps)
                    named += r["ms"] / len(steps)
        print("in instructions named after a collective (what "
              "exposed_collective_ms.train counts): %.3f ms a step = %s" % (
                  named, " + ".join("%s %.3f" % (a or "-", ms) for a, ms in
                                    sorted(by_axis.items(),
                                           key=lambda kv: -kv[1]))))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0].startswith("-"):
        sys.stderr.write(__doc__.split("\n\n")[1] + "\n")
        return 2
    account = account_of_dir(argv[0])
    if account is None:
        sys.stderr.write("no operation in a *.xplane.pb under %s\n" % argv[0])
        return 1
    print_tables(account, int(argv[1]) if len(argv) > 1 else 40)
    return 0


if __name__ == "__main__":
    sys.exit(main())
