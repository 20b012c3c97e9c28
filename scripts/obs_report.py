#!/usr/bin/env python
"""Join a Timeline JSON and a metrics JSONL into one observability report.

Usage:
    scripts/obs_report.py --timeline TL.json --metrics METRICS.jsonl \
        [--flight FLIGHT_DIR] [--json OUT.json]

Produce the artifacts with any training run::

    HOROVOD_TIMELINE=tl.json HOROVOD_METRICS_JSONL=metrics.jsonl \
        python examples/jax_synthetic.py

Report sections (docs/observability.md):

* **Phase breakdown** — per-activity span time from the Timeline
  (OVERLAP:*, SERVE:*, PROFILE:*, ...), audited for B/E balance
  (monitor/span_audit.py);
* **Stall table** — every STALL:* instant with rank attribution, plus
  the stall.warnings counters;
* **Overlap** — comm_hidden_fraction recomputed from the registry's
  comm.wire.* gauges (overlap / (ici + dcn) bytes of the last traced
  program) — reproduces ``WireStats.hidden_fraction``
  (tests/test_overlap.py);
* **Wire budget** — measured per-device wire bytes per hop vs the
  modeled transfer time at HOROVOD_BENCH_ICI_GBPS/DCN_GBPS
  (``plan.accounting.bench_gbps``), and the DCN
  fp-equivalent reduction of the quantized wire;
* **Straggler table** — per-rank per-phase skew from the
  ``straggler.*`` gauges (monitor/straggler.py), detections, step-skew
  gauges, and the cost-model-backed ``link.health{hop}`` scores;
* **Flight records** — with ``--flight DIR`` (or
  HOROVOD_FLIGHT_RECORDER_DIR set), the ``scripts/postmortem.py``
  cross-rank join of any dumps present.

Exit 0 on success, 2 on usage/artifact errors. ``--json`` additionally
writes the report as one machine-readable dict.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.monitor.span_audit import (  # noqa: E402
    SpanImbalanceError, audit_spans, load_events)


def load_metrics(path):
    """All snapshots in the JSONL; the LAST one is the report's state."""
    snaps = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "metrics":
                snaps.append(rec)
    return snaps


def hidden_fraction(gauges):
    total = (gauges.get("comm.wire.ici_bytes", 0.0)
             + gauges.get("comm.wire.dcn_bytes", 0.0)
             + gauges.get("comm.wire.pod_bytes", 0.0))
    if not total:
        return 0.0
    return gauges.get("comm.wire.overlap_bytes", 0.0) / total


def straggler_section(counters, gauges):
    """Per-rank per-phase matrix + detections + link health from the
    registry families monitor/straggler.py publishes."""
    import re

    phase_re = re.compile(
        r"^straggler\.phase_ms\{phase=([^,}]+),rank=(\d+)\}$")
    matrix = {}
    for k, v in gauges.items():
        m = phase_re.match(k)
        if m:
            matrix.setdefault(int(m.group(2)), {})[m.group(1)] = v
    det_re = re.compile(
        r"^straggler\.detected\{phase=([^,}]+),rank=(\d+)\}$")
    detected = [{"rank": int(m.group(2)), "phase": m.group(1), "count": v}
                for k, v in counters.items()
                for m in [det_re.match(k)] if m]
    skew = {k.split("phase=", 1)[1].rstrip("}"): v
            for k, v in gauges.items() if k.startswith("step.skew_ms{")}
    link = {k.split("hop=", 1)[1].rstrip("}"): v
            for k, v in gauges.items() if k.startswith("link.health{")}
    degraded = {k.split("hop=", 1)[1].rstrip("}"): v
                for k, v in counters.items()
                if k.startswith("straggler.link_degraded{")}
    return {
        "phase_ms_by_rank": {str(r): matrix[r] for r in sorted(matrix)},
        "detected": sorted(detected,
                           key=lambda d: (d["rank"], d["phase"])),
        "step_skew_ms": skew,
        "link_health": link,
        "link_degraded": degraded,
    }


def flight_section(flight_dir):
    """The postmortem join of any flight dumps present (None when the
    directory is unset/empty — a healthy run has no dumps)."""
    if not flight_dir or not os.path.isdir(flight_dir):
        return None
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "postmortem.py")
    spec = importlib.util.spec_from_file_location("_postmortem", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.build_report(flight_dir)
    return report if report["dumps"] else None


def prometheus_discovery(metrics_path):
    """The ``<jsonl>.port`` endpoint-discovery file the PrometheusSink
    leaves when HOROVOD_METRICS_PORT resolves a port (0 = ephemeral)."""
    try:
        with open(metrics_path + ".port") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def build_report(timeline_path, metrics_path, flight_dir=None):
    events = load_events(timeline_path)
    try:
        audit = audit_spans(events)
        balanced, imbalance = True, None
    except SpanImbalanceError as e:
        audit = audit_spans(events, require_balanced=False)
        balanced, imbalance = False, str(e)

    snaps = load_metrics(metrics_path)
    if not snaps:
        raise SystemExit(f"no metrics snapshots in {metrics_path}")
    snap = snaps[-1]
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    hists = snap.get("histograms", {})

    stalls = [
        {"name": ev["name"], "ts_us": ev.get("ts"),
         **(ev.get("args") or {})}
        for ev in events
        if ev.get("ph") == "i" and str(ev.get("name", "")).startswith("STALL:")]
    stall_warnings = sum(v for k, v in counters.items()
                         if k.startswith("stall.warnings"))

    ici = gauges.get("comm.wire.ici_bytes", 0.0)
    dcn = gauges.get("comm.wire.dcn_bytes", 0.0)
    dcn_fp = gauges.get("comm.wire.dcn_bytes_fp", 0.0)
    pod = gauges.get("comm.wire.pod_bytes", 0.0)
    from horovod_tpu.plan.accounting import bench_gbps

    ici_gbps, dcn_gbps, pod_gbps = bench_gbps()
    return {
        "timeline": os.path.abspath(timeline_path),
        "metrics": os.path.abspath(metrics_path),
        "snapshots": len(snaps),
        "events": len(events),
        "spans_balanced": balanced,
        "span_imbalance": imbalance,
        "total_spans": audit.total_spans,
        "phase_time_us": {k: round(v, 1)
                          for k, v in sorted(audit.by_phase().items())},
        "activity_time_us": {k: round(v, 1)
                             for k, v in sorted(audit.duration_us.items())},
        "stalls": stalls,
        "stall_warnings": stall_warnings,
        "comm_hidden_fraction": hidden_fraction(gauges),
        "wire_budget": {
            "ici_bytes_per_step_device": ici,
            "dcn_bytes_per_step_device": dcn,
            "dcn_bytes_fp_equiv": dcn_fp,
            "dcn_reduction": (dcn_fp / dcn) if dcn else None,
            "pod_bytes_per_step_device": pod,
            "modeled_wire_ms": round(
                (ici / (ici_gbps * 1e9) + dcn / (dcn_gbps * 1e9)
                 + pod / (pod_gbps * 1e9)) * 1e3, 4),
            "model": {"ici_gbps": ici_gbps, "dcn_gbps": dcn_gbps,
                      "pod_gbps": pod_gbps},
        },
        "streamed_buckets": gauges.get("comm.wire.streamed_buckets", 0.0),
        "step_time_hist": hists.get("step.time_ms"),
        "eager_calls": {k: v for k, v in counters.items()
                        if k.startswith("comm.eager.calls")},
        "serve": {k: v for k, v in {**counters, **gauges}.items()
                  if k.startswith("serve.")},
        "straggler": straggler_section(counters, gauges),
        "flight": flight_section(
            flight_dir or os.environ.get("HOROVOD_FLIGHT_RECORDER_DIR")),
        "prometheus": prometheus_discovery(metrics_path),
    }


def print_report(r):
    w = print
    w(f"== observability report ==")
    w(f"timeline: {r['timeline']} ({r['events']} events, "
      f"{r['total_spans']} spans, "
      f"{'balanced' if r['spans_balanced'] else 'IMBALANCED: ' + str(r['span_imbalance'])})")
    w(f"metrics:  {r['metrics']} ({r['snapshots']} snapshots)")
    w("")
    w("-- phase time breakdown (host spans) --")
    if r["activity_time_us"]:
        for name, us in sorted(r["activity_time_us"].items(),
                               key=lambda kv: -kv[1]):
            w(f"  {name:<32} {us / 1e3:10.3f} ms")
    else:
        w("  (no spans)")
    w("")
    w("-- stalls --")
    if r["stalls"]:
        for s in r["stalls"]:
            w(f"  {s['name']:<40} rank {s.get('rank', '?')} "
              f"elapsed {s.get('elapsed_secs', '?')}s "
              f"missing {s.get('missing_ranks', '?')}")
    w(f"  stall warnings (registry): {r['stall_warnings']:g}")
    w("")
    w("-- overlap --")
    w(f"  comm_hidden_fraction: {r['comm_hidden_fraction']:.4f} "
      f"({r['streamed_buckets']:g} streamed buckets)")
    w("")
    w("-- wire budget (per step, per device) --")
    wb = r["wire_budget"]
    w(f"  ICI {wb['ici_bytes_per_step_device'] / 1e6:.3f} MB, "
      f"DCN {wb['dcn_bytes_per_step_device'] / 1e6:.3f} MB"
      + (f" (fp-equiv {wb['dcn_bytes_fp_equiv'] / 1e6:.3f} MB, "
         f"{wb['dcn_reduction']:.2f}x reduction)"
         if wb["dcn_reduction"] else ""))
    w(f"  modeled transfer: {wb['modeled_wire_ms']} ms at "
      f"ICI {wb['model']['ici_gbps']} GB/s / DCN {wb['model']['dcn_gbps']} GB/s")
    if r["serve"]:
        w("")
        w("-- serve --")
        for k, v in sorted(r["serve"].items()):
            w(f"  {k:<40} {v:g}")
    st = r.get("straggler") or {}
    if st.get("phase_ms_by_rank") or st.get("link_health"):
        w("")
        w("-- stragglers --")
        for rank, phases in st.get("phase_ms_by_rank", {}).items():
            row = "  ".join(f"{p}={ms:.1f}ms"
                            for p, ms in sorted(phases.items()) if ms)
            w(f"  rank {rank:<4} {row or '(no phases recorded)'}")
        for p, v in sorted(st.get("step_skew_ms", {}).items()):
            w(f"  skew {p:<12} {v:.2f} ms (max - median across ranks)")
        for d in st.get("detected", []):
            w(f"  DETECTED rank {d['rank']} phase {d['phase']} "
              f"(x{d['count']:g})")
        for hop, v in sorted(st.get("link_health", {}).items()):
            flag = "  DEGRADED" if st.get("link_degraded", {}).get(hop) \
                else ""
            w(f"  link {hop:<4} health {v:.2f} "
              f"(measured/predicted wire-ms){flag}")
    if r.get("prometheus"):
        w("")
        w(f"-- prometheus: {r['prometheus'].get('endpoint')} "
          f"(pid {r['prometheus'].get('pid')}) --")
    if r.get("flight"):
        fl = r["flight"]
        w("")
        w(f"-- flight records ({fl['dumps']} dump(s) in "
          f"{fl['directory']}) --")
        for key, row in fl["ranks"].items():
            mark = " CRASHED" if row["crashed"] else ""
            w(f"  {key:<14} reason={row['reason']} "
              f"last_step={row['last_step']}{mark}")
        if fl["crashed_ranks"]:
            w(f"  crashing rank(s): {', '.join(fl['crashed_ranks'])}; "
              f"last common step {fl['last_common_step']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeline", required=True)
    ap.add_argument("--metrics", required=True)
    ap.add_argument("--flight", default=None,
                    help="flight-record dump dir (default: "
                         "HOROVOD_FLIGHT_RECORDER_DIR)")
    ap.add_argument("--json", help="also write the report dict here")
    args = ap.parse_args()
    for p in (args.timeline, args.metrics):
        if not os.path.exists(p):
            ap.error(f"no such file: {p}")
    report = build_report(args.timeline, args.metrics,
                          flight_dir=args.flight)
    print_report(report)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
