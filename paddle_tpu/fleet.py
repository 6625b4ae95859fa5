"""Fleet observability: per-collective bandwidth attribution, cross-host
straggler detection, and goodput accounting (ISSUE 8 tentpole).

Extends the single-host observability stack (telemetry / memory /
roofline) across the mesh and the fleet, three layers:

1. **Per-collective attribution** — the collective rows of the step's
   account by instruction (xplane.step_account: the trace's `XLA Ops`
   events joined to the compiled blocks' instructions), classified into
   collective kinds (xplane.COLLECTIVE_KINDS) and joined to framework
   call sites through `pd.coll.<site>` named scopes
   (parallel/_collectives.coll_scope) landing in HLO metadata op_name.
   Each (kind, site, mesh axis) row carries bytes moved (HLO output
   shapes), device time, the exposed-vs-overlapped split
   (xplane.exposed_in_line), and
   achieved bus bandwidth with the nccl-tests algbw→busbw factors —
   judged against the measured ICI/DCN link roofline
   (roofline.ensure_ici, PADDLE_TPU_ICI_GBPS override) as `% of link`.

2. **Cross-host skew** — a FleetSnapshot per host (step time, device
   duty cycle, infeed wait, collective wait, hbm gauges) allgathered
   over the jax.distributed coordination service
   (multihost.allgather_bytes — control plane, works on the CPU
   backend), reduced into max/median step-time skew and a slowest-host
   attribution (compute vs infeed vs collective-wait), published as the
   `fleet_step_skew` / `fleet_straggler_host` gauges.

3. **Goodput accounting** — the run ledger: wall span split into
   productive step time vs badput buckets (compile, checkpoint save,
   restore, input stall, collective wait, idle) from telemetry events
   already emitted by the executor, io.py and multihost checkpointing.
   Published as `goodput_fraction` + `goodput_seconds{bucket}`.

Consumers: `python -m paddle_tpu fleet` (CLI), `perf`'s report
(roofline.collect_report embeds `collectives`) and
profiler.stop_profiler (fleet summary line when multi-process).
"""

from __future__ import annotations

import json
import shutil
import tempfile
from typing import Any, Dict, List, Optional

__all__ = ["collective_table", "local_snapshot", "fleet_snapshot",
           "goodput_report", "format_goodput", "format_fleet", "capture"]

UNATTRIBUTED = "(unattributed)"


# --- per-collective bandwidth attribution -----------------------------------

def collective_table(trace_dir, steps: Optional[int] = None,
                     probe: bool = True, accounts=None,
                     account=None) -> Dict[str, Any]:
    """The collective rows of the trace's account by instruction
    (`xplane.step_account`; `account` hands in one already joined,
    `accounts` the instruction lists to join with) folded into
    per-(kind, site, mesh axis) rows:

        {"rows": [{kind, site, axis, group_size, count, bytes, time_ms,
                   exposed_ms, algbw_gbps, busbw_gbps, pct_link,
                   overlap_frac}],
         "ici_gbps": float|None, "participants": int|None}

    `bytes` are per traced session (each instruction's payload × its
    runs); busbw uses the nccl-tests factor for the kind at the
    instruction's OWN group size (a tp all-reduce over 2 of 4 chips is
    not a 4-way one), judged against the link roofline when the ICI
    probe (or PADDLE_TPU_ICI_GBPS) is available. `axis` is the mesh axis
    the instruction's replica groups run along ('tp', 'fsdp', 'fsdp+tp'
    where a group spans both; None without the planner's mesh).
    Instructions with no pd.coll scope pool under "(gspmd:<op>)" — the
    partitioner-inserted collectives (dp grad all-reduce,
    tensor-parallel gathers) that no framework line emits directly,
    named after the program op they were split from. A collective event
    no account names keeps its kind and its time."""
    from . import roofline, xplane

    if account is None:
        account = xplane.step_account(trace_dir, accounts=accounts)
    by_site: Dict[tuple, Dict[str, float]] = {}
    participants = None
    for step in (account or {}).get("steps", ()):
        for r in step["rows"]:
            if not r["kind"]:
                continue
            site = r["site"]
            if site is None:
                site = f"(gspmd:{r['op']})" if r["op"] else "(gspmd)"
            acc = by_site.setdefault((r["kind"], site, r["axis"]), {
                "count": 0, "bytes": 0.0, "ms": 0.0, "exposed_ms": 0.0,
                "bus_bytes": 0.0, "group_size": r["group_size"]})
            acc["count"] += r["count"]
            acc["bytes"] += float(r["payload"]) * r["count"]
            acc["ms"] += r["ms"]
            acc["exposed_ms"] += r.get("exposed_ms", r["ms"])
            n = r["group_size"] or 1
            acc["bus_bytes"] += float(r["payload"]) * r["count"] \
                * xplane.busbw_factor(r["kind"], n)
            participants = max(participants or 0, n)
    if participants is None:
        try:
            import jax
            participants = jax.device_count()
        except Exception:  # noqa: BLE001 - stdlib-only callers
            participants = None

    ici = roofline.ensure_ici(probe) if (by_site or probe) else None
    rows: List[Dict[str, Any]] = []
    for (kind, site, axis), acc in sorted(by_site.items(),
                                          key=lambda kv: -kv[1]["ms"]):
        secs = acc["ms"] / 1e3
        algbw = (acc["bytes"] / secs / 1e9) if secs > 0 else None
        busbw = (acc["bus_bytes"] / secs / 1e9) if secs > 0 else None
        if busbw == 0.0:
            busbw = algbw
        pct = (busbw / ici) if (busbw is not None and ici) else None
        rows.append({
            "kind": kind, "site": site, "axis": axis,
            "group_size": acc["group_size"], "count": acc["count"],
            "bytes": acc["bytes"], "time_ms": acc["ms"],
            "exposed_ms": acc["exposed_ms"],
            "algbw_gbps": algbw, "busbw_gbps": busbw, "pct_link": pct,
            "overlap_frac": (1.0 - acc["exposed_ms"] / acc["ms"]
                             if acc["ms"] else None)})
    return {"rows": rows, "ici_gbps": ici, "participants": participants}


# --- cross-host skew / straggler detection ----------------------------------

def local_snapshot() -> Dict[str, Any]:
    """This host's FleetSnapshot: the per-host scalars the skew reduce
    compares. All reads are read-only telemetry peeks — a host that never
    ran a step contributes zeros, never new series."""
    from . import telemetry

    hist = telemetry.read_histogram("input_stall_seconds") or {}
    hbm = {name: max(telemetry.read_series(name).values() or [0.0])
           for name in ("hbm_bytes_in_use", "hbm_peak_bytes")}
    # run-sentinel alert counts (ISSUE 17): series keys are
    # "rule=...,severity=..." — page-severity split out so the straggler
    # verdict can say WHICH host is alerting, not just which is slow
    alerts = telemetry.read_series("sentinel_alerts_total")
    alerts_page = sum(v for k, v in alerts.items() if "severity=page" in k)
    return {
        "host": telemetry._host_index(),
        "steps": sum(telemetry.read_series("executor_steps_total")
                     .values() or [0.0]),
        "step_time_s": telemetry.read_gauge("executor_last_step_seconds"),
        "device_duty_cycle": telemetry.read_gauge("device_duty_cycle"),
        "infeed_wait_s": hist.get("sum", 0.0),
        "collective_wait_s":
            telemetry.read_gauge("collective_exposed_seconds") or 0.0,
        "collective_time_s":
            telemetry.read_gauge("collective_time_seconds") or 0.0,
        "hbm_bytes_in_use": hbm["hbm_bytes_in_use"],
        "hbm_peak_bytes": hbm["hbm_peak_bytes"],
        "alerts_total": sum(alerts.values()) if alerts else 0.0,
        "alerts_page": alerts_page,
    }


def fleet_snapshot(local: Optional[Dict[str, Any]] = None) \
        -> Dict[str, Any]:
    """Allgather every host's FleetSnapshot and reduce: max/median
    step-time skew, the slowest host, and what it is slow ON (compute vs
    infeed vs collective-wait, by largest excess over the fleet median).
    Publishes `fleet_step_skew` and `fleet_straggler_host`. Single-process
    runs short-circuit to a skew of 1.0 with themselves as the (vacuous)
    straggler."""
    from . import telemetry
    from .parallel import multihost

    local = dict(local or local_snapshot())
    payloads = multihost.allgather_bytes(
        json.dumps(local, sort_keys=True).encode("utf-8"))
    hosts = []
    for p in payloads:
        try:
            hosts.append(json.loads(p.decode("utf-8")))
        except Exception:  # noqa: BLE001 - a corrupt peer can't kill us
            continue
    if not hosts:
        hosts = [local]

    def _med(vals):
        vals = sorted(vals)
        k = len(vals) // 2
        return vals[k] if len(vals) % 2 else 0.5 * (vals[k - 1] + vals[k])

    times = [float(h.get("step_time_s") or 0.0) for h in hosts]
    med = _med(times)
    mx = max(times)
    skew = (mx / med) if med > 0 else 1.0
    slow = hosts[times.index(mx)]

    # attribution: which badput component exceeds the fleet median most
    cause, excess = "compute", 0.0
    for key, label in (("infeed_wait_s", "infeed"),
                       ("collective_wait_s", "collective-wait")):
        vals = [float(h.get(key) or 0.0) for h in hosts]
        d = float(slow.get(key) or 0.0) - _med(vals)
        if d > excess:
            cause, excess = label, d
    # sentinel alert roll-up: the host with the most alerts, so a skew
    # verdict can name the host that is also statistically anomalous
    alert_counts = [float(h.get("alerts_total") or 0.0) for h in hosts]
    alerting = (hosts[alert_counts.index(max(alert_counts))]
                if max(alert_counts, default=0.0) > 0 else None)
    out = {
        "hosts": hosts, "n_hosts": len(hosts),
        "median_step_s": med, "max_step_s": mx,
        "step_skew": max(skew, 1.0),
        "straggler": {"host": slow.get("host", 0), "cause": cause,
                      "alerts_total": float(slow.get("alerts_total")
                                            or 0.0)},
        "alerting_host": (None if alerting is None
                          else {"host": alerting.get("host", 0),
                                "alerts_total":
                                    float(alerting.get("alerts_total")
                                          or 0.0),
                                "alerts_page":
                                    float(alerting.get("alerts_page")
                                          or 0.0)}),
    }
    telemetry.gauge(
        "fleet_step_skew",
        "max/median step-time ratio across hosts (1.0 = no skew)").set(
            out["step_skew"])
    telemetry.gauge(
        "fleet_straggler_host",
        "host index with the slowest last step").set(
            float(out["straggler"]["host"]))
    return out


# --- goodput accounting ------------------------------------------------------

_RUN_KINDS = ("run", "run_window")


def goodput_report(events=None, now: Optional[float] = None,
                   input_stall_s: Optional[float] = None,
                   collective_wait_s: Optional[float] = None) \
        -> Optional[Dict[str, Any]]:
    """The run-level goodput ledger. Wall span = first run start to last
    run end (telemetry event `mono` stamps); split into:

        productive       execute time minus exposed collective wait
        compile          run.compile_s sums (the `compile` events are
                         nested inside run wall time — counting both
                         would double-price a trace)
        checkpoint_save  multihost 'checkpoint' op=save events, falling
                         back to io.py 'checkpoint_save' (which nest
                         inside multihost saves — never both)
        restore          ... same for load
        input_stall      input_stall_seconds histogram sum
        collective_wait  exposed collective seconds (trace-derived)
        idle             span minus everything above (clamped ≥ 0)

    Returns None with no run events (nothing ran — no denominator).
    Publishes `goodput_fraction` + `goodput_seconds{bucket}`."""
    from . import telemetry

    events = list(telemetry.recent_events() if events is None else events)
    runs = [e for e in events if e.get("kind") in _RUN_KINDS]
    if not runs:
        return None
    starts = [e["mono"] - float(e.get("seconds") or 0.0) for e in runs]
    ends = [e["mono"] for e in runs]
    span = (now if now is not None else max(ends)) - min(starts)
    span = max(span, 1e-9)

    execute = sum(float(e.get("execute_s") or 0.0) for e in runs)
    compile_ = sum(float(e.get("compile_s") or 0.0) for e in runs)

    # checkpoint badput: prefer the multihost wall-clock markers; io.py's
    # save/load events nest inside them, so fall back only when no
    # multihost marker of that direction exists
    mh = [e for e in events if e.get("kind") == "checkpoint"]
    ck_save = sum(float(e.get("seconds") or 0.0) for e in mh
                  if e.get("op") == "save")
    ck_load = sum(float(e.get("seconds") or 0.0) for e in mh
                  if e.get("op") == "load")
    if not any(e.get("op") == "save" for e in mh):
        ck_save = sum(float(e.get("seconds") or 0.0) for e in events
                      if e.get("kind") == "checkpoint_save")
    if not any(e.get("op") == "load" for e in mh):
        ck_load = sum(float(e.get("seconds") or 0.0) for e in events
                      if e.get("kind") == "checkpoint_load")

    if input_stall_s is None:
        hist = telemetry.read_histogram("input_stall_seconds") or {}
        input_stall_s = float(hist.get("sum", 0.0))
    if collective_wait_s is None:
        collective_wait_s = float(
            telemetry.read_gauge("collective_exposed_seconds") or 0.0)
    collective_wait_s = min(collective_wait_s, execute)

    productive = max(execute - collective_wait_s, 0.0)
    buckets = {
        "productive": productive,
        "compile": compile_,
        "checkpoint_save": ck_save,
        "restore": ck_load,
        "input_stall": input_stall_s,
        "collective_wait": collective_wait_s,
    }
    accounted = sum(buckets.values())
    buckets["idle"] = max(span - accounted, 0.0)
    goodput = min(productive / span, 1.0)

    g = telemetry.gauge("goodput_fraction",
                        "productive step time / wall span of the run")
    g.set(goodput)
    bg = telemetry.gauge("goodput_seconds",
                         "wall seconds per goodput/badput bucket",
                         labels=("bucket",))
    for b, v in buckets.items():
        bg.labels(bucket=b).set(v)
    return {"span_s": span, "goodput_fraction": goodput,
            "buckets": buckets, "runs": len(runs)}


# --- rendering ---------------------------------------------------------------

def format_goodput(gp: Optional[Dict[str, Any]]) -> List[str]:
    if not gp:
        return ["[goodput] no run events recorded"]
    lines = ["[goodput] {:.1%} productive over {:.2f}s wall "
             "({} runs)".format(gp["goodput_fraction"], gp["span_s"],
                                gp["runs"])]
    span = gp["span_s"]
    for bucket, v in sorted(gp["buckets"].items(), key=lambda kv: -kv[1]):
        lines.append("[goodput]   {:16s} {:9.3f}s {:6.1%}".format(
            bucket, v, v / span))
    return lines


def format_fleet(snap: Dict[str, Any]) -> str:
    s = snap["straggler"]
    line = ("[fleet] hosts {} | step skew {:.2f}x (median {:.4f}s, max "
            "{:.4f}s) | straggler host {} ({})".format(
                snap["n_hosts"], snap["step_skew"], snap["median_step_s"],
                snap["max_step_s"], s["host"], s["cause"]))
    a = snap.get("alerting_host")
    if a:
        line += " | alerting host {} ({:.0f} alert(s))".format(
            a["host"], a["alerts_total"])
    return line


# --- one-call capture --------------------------------------------------------

def capture(run, steps: int = 3, probe: bool = True) \
        -> Optional[Dict[str, Any]]:
    """Run `run()` `steps` times inside a silent traced session and return
    {"roofline", "collectives", "goodput", "snapshot"} — the fleet
    analogue of roofline.capture (which it reuses; the roofline report
    already embeds the collective table). None when tracing failed."""
    from . import profiler as profiler_mod

    tmp = tempfile.mkdtemp(prefix="pd_fleet_")
    report = None
    try:
        profiler_mod.start_profiler(trace_dir=tmp)
        try:
            for _ in range(steps):
                run()
        finally:
            report = profiler_mod.finish_trace_report(probe=probe)
    except Exception:  # noqa: BLE001 - observability must not kill the run
        report = None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if report is None:
        return None
    try:
        snap = fleet_snapshot()
    except Exception:  # noqa: BLE001
        snap = None
    return {"roofline": report,
            "collectives": report.get("collectives"),
            "goodput": goodput_report(),
            "snapshot": snap}
