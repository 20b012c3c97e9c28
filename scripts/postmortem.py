#!/usr/bin/env python
"""Join all ranks' flight-record dumps into one crash postmortem.

Usage:
    scripts/postmortem.py --dir FLIGHT_DIR [--json OUT.json]

Every process of a run armed with ``HOROVOD_FLIGHT_RECORDER_DIR`` leaves
``flight_*.json`` dumps there on its crash paths (unhandled exception,
SIGTERM, chaos ``crash``, StallInspector escalation, elastic
reset/abandon — monitor/flight.py). This tool verifies each dump's crc32
(torn files are reported, never trusted), groups them by rank, and
answers the three questions an on-call asks first
(docs/observability.md):

* **Who died, and of what?** Per-rank last dump reason + last recorded
  event; crash-class reasons (``chaos.crash``, ``exception``,
  ``sigterm``, ``stall.escalation``) name the crashing rank(s).
* **Where did the job diverge?** The last step/commit every rank
  reached; the *last common step* is the highest step all ranks
  completed, the *divergence step* the first step some rank is missing.
* **What was in flight?** Each rank's in-flight collectives and stalled
  tensors at dump time, plus the straggler-detection history leading up
  to the crash (was the dead rank dragging before it died?).

Exit 0 on success, 2 when the directory holds no parseable dumps.
``--json`` writes the machine-readable report (what the chaos tests
assert on).
"""

import argparse
import glob
import json
import os
import sys
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: Dump reasons that mean "this rank died here" (vs a survivor's
#: reset/abandon bookkeeping dump).
CRASH_REASONS = ("chaos.crash", "exception", "sigterm",
                 "stall.escalation")


def load_dumps(directory):
    """(dumps, corrupt) — parsed dumps with verified event crc32s, and
    the [(path, why)] list of files that failed."""
    dumps, corrupt = [], []
    for path in sorted(glob.glob(os.path.join(directory, "flight_*.json"))):
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError) as e:
            corrupt.append((path, f"unreadable: {e}"))
            continue
        want = d.get("events_crc32")
        payload = json.dumps(d.get("events", []), sort_keys=True).encode()
        got = f"crc32:{zlib.crc32(payload) & 0xFFFFFFFF:08x}"
        if want != got:
            corrupt.append((path, f"checksum mismatch: {want} != {got}"))
            continue
        d["_path"] = path
        dumps.append(d)
    return dumps, corrupt


def _rank_key(dump):
    """Stable per-process key: the rank when known, else the
    host:local_rank identity, else driver/pid."""
    ident = dump.get("identity", {})
    rank = ident.get("rank", -1)
    if isinstance(rank, int) and rank >= 0:
        return f"rank{rank}"
    host = ident.get("hostname") or ""
    lr = ident.get("local_rank") or ""
    if host:
        return f"{host}:{lr}"
    return ident.get("role") or f"pid{ident.get('pid', '?')}"


def _last_step(events):
    """Highest completed step/commit mark in an event list (None when
    the rank never marked one)."""
    last = None
    for ev in events:
        args = ev.get("args") or {}
        n = None
        if ev.get("name") == "FLIGHT:STEP":
            n = args.get("step")
        elif ev.get("name") == "FLIGHT:COMMIT":
            n = args.get("batch")
        if n is not None:
            last = n if last is None else max(last, n)
    return last


def _summarize_rank(dumps):
    """One report row per process key, from its LATEST dump (earlier
    dumps of the same process still contribute step marks)."""
    latest = max(dumps, key=lambda d: d.get("ts", 0.0))
    events = latest.get("events", [])
    last_ev = events[-1] if events else None
    steps = [s for s in (_last_step(d.get("events", [])) for d in dumps)
             if s is not None]
    faults = {}
    for ev in events:
        name = str(ev.get("name", ""))
        if name.startswith("FAULT:"):
            faults[name[len("FAULT:"):]] = \
                faults.get(name[len("FAULT:"):], 0) + 1
    return {
        "identity": latest.get("identity", {}),
        "dumps": len(dumps),
        "path": latest.get("_path"),
        "reason": latest.get("reason"),
        "ts": latest.get("ts"),
        "crashed": latest.get("reason") in CRASH_REASONS,
        "last_step": max(steps) if steps else None,
        "events": len(events),
        "last_event": ({"name": last_ev.get("name"),
                        "wall": last_ev.get("wall"),
                        "args": last_ev.get("args")}
                       if last_ev else None),
        "in_flight": latest.get("in_flight", []),
        "stalled": latest.get("stalled", []),
        "faults": faults,
        "straggler": latest.get("straggler", []),
        "expert_load": latest.get("expert_load") or {},
        "serve_cache": latest.get("serve_cache") or {},
        "extra": latest.get("extra"),
    }


def build_report(directory):
    dumps, corrupt = load_dumps(directory)
    by_key = {}
    for d in dumps:
        by_key.setdefault(_rank_key(d), []).append(d)
    ranks = {k: _summarize_rank(v) for k, v in sorted(by_key.items())}

    worker_rows = {k: r for k, r in ranks.items()
                   if r["identity"].get("role") != "driver"}
    steps = {k: r["last_step"] for k, r in worker_rows.items()
             if r["last_step"] is not None}
    last_common = min(steps.values()) if steps else None
    max_step = max(steps.values()) if steps else None
    crashed = sorted(k for k, r in ranks.items() if r["crashed"])
    # Divergence: the first step NOT completed by every rank — set when
    # some rank got further than another, or when a crash-class dump
    # exists (the crashed rank died inside step last_common + 1 even if
    # its peers rolled back to the same commit).
    divergence = (last_common + 1
                  if last_common is not None
                  and (crashed or (max_step is not None
                                   and max_step > last_common))
                  else None)
    laggards = []
    if divergence is not None:
        laggards = sorted(k for k, s in steps.items() if s < max_step)
        if not laggards:
            laggards = [k for k in crashed if k in worker_rows]
    straggler_history = []
    for r in ranks.values():
        straggler_history.extend(r["straggler"])
    straggler_history.sort(key=lambda d: d.get("ts", 0.0))
    # Per-expert load (docs/moe.md): merge every rank's expert_load so
    # the postmortem can NAME the hot expert a skewed run died under.
    expert_load = {}
    for r in ranks.values():
        for e, tokens in (r.get("expert_load") or {}).items():
            expert_load[e] = expert_load.get(e, 0.0) + float(tokens)
    hot_expert = None
    if expert_load:
        total = sum(expert_load.values())
        if total > 0:
            e, tokens = max(expert_load.items(), key=lambda kv: kv[1])
            hot_expert = {"expert": e, "tokens": tokens,
                          "share": round(tokens / total, 4)}
    # Disaggregated-serving view (docs/serving.md): merge every rank's
    # serve_cache snapshot (scalars take the max — each rank reports its
    # own fleet totals — and the per-replica stall map folds by sum) so
    # the postmortem can NAME the replica that idled on a migration.
    serve_cache = {}
    for r in ranks.values():
        for key, val in (r.get("serve_cache") or {}).items():
            if isinstance(val, dict):
                bucket = serve_cache.setdefault(key, {})
                for sub, x in val.items():
                    bucket[sub] = bucket.get(sub, 0.0) + float(x)
            else:
                serve_cache[key] = max(
                    float(val), float(serve_cache.get(key, 0.0)))
    stalled_replica = None
    stall_by = serve_cache.get("stall_steps_by_replica") or {}
    if stall_by:
        name, steps_stalled = max(stall_by.items(), key=lambda kv: kv[1])
        if steps_stalled > 0:
            stalled_replica = {"replica": name, "stall_steps": steps_stalled}
    return {
        "directory": os.path.abspath(directory),
        "dumps": len(dumps),
        "corrupt": [{"path": p, "error": e} for p, e in corrupt],
        "ranks": ranks,
        "last_common_step": last_common,
        "max_step": max_step,
        "divergence_step": divergence,
        "crashed_ranks": crashed,
        "diverged_ranks": laggards,
        "straggler_history": straggler_history,
        "expert_load": expert_load,
        "hot_expert": hot_expert,
        "serve_cache": serve_cache,
        "migration_stalled_replica": stalled_replica,
    }


def print_report(r):
    w = print
    w("== flight-record postmortem ==")
    w(f"directory: {r['directory']} ({r['dumps']} dump(s), "
      f"{len(r['corrupt'])} corrupt)")
    for c in r["corrupt"]:
        w(f"  CORRUPT {c['path']}: {c['error']}")
    w("")
    w("-- per-rank summary --")
    for key, row in r["ranks"].items():
        mark = " <-- CRASHED" if row["crashed"] else ""
        step = row["last_step"] if row["last_step"] is not None else "?"
        last = row["last_event"]["name"] if row["last_event"] else "(none)"
        w(f"  {key:<14} reason={row['reason']:<16} last_step={step:<6} "
          f"events={row['events']:<5} last_event={last}{mark}")
        if row["in_flight"]:
            w(f"  {'':<14} in flight: {', '.join(row['in_flight'])}")
        for s in row["stalled"]:
            w(f"  {'':<14} stalled: {s.get('name')} "
              f"({s.get('elapsed_secs', 0):.1f}s)")
    w("")
    w("-- verdict --")
    if r["crashed_ranks"]:
        w(f"  crashing rank(s): {', '.join(r['crashed_ranks'])}")
    else:
        w("  no crash-class dump found (resets/abandons only)")
    lc = r["last_common_step"]
    w(f"  last common step: {lc if lc is not None else 'unknown'}")
    if r["divergence_step"] is not None:
        w(f"  divergence at step {r['divergence_step']}: "
          f"{', '.join(r['diverged_ranks'])} never completed it "
          f"(furthest rank reached {r['max_step']})")
    if r.get("hot_expert"):
        he = r["hot_expert"]
        w(f"  hot expert: expert {he['expert']} carried "
          f"{he['share']:.0%} of the MoE load "
          f"({he['tokens']:.0f} tokens) — docs/moe.md")
    if r.get("migration_stalled_replica"):
        ms = r["migration_stalled_replica"]
        w(f"  migration-stalled replica: {ms['replica']} idled "
          f"{ms['stall_steps']:.0f} decode step(s) waiting on KV "
          f"migrations — docs/serving.md")
    sc = r.get("serve_cache") or {}
    if sc:
        hits = sc.get("serve.prefix_hits")
        rate = sc.get("serve.prefix_hit_rate")
        acc = sc.get("serve.spec.acceptance_rate")
        migs = sc.get("serve.kv.migrations")
        parts = []
        if hits is not None and rate is not None:
            parts.append(f"prefix hits {hits:.0f} (rate {rate:.2f})")
        if acc is not None:
            parts.append(f"spec acceptance {acc:.2f}")
        if migs is not None:
            parts.append(f"kv migrations {migs:.0f}")
        if parts:
            w(f"  serving cache: {', '.join(parts)}")
    if r["straggler_history"]:
        w("")
        w("-- straggler history (pre-crash) --")
        for d in r["straggler_history"][-10:]:
            if d.get("kind") == "link":
                w(f"  rank {d.get('rank')} link {d.get('hop')} "
                  f"health {d.get('ratio')} > gate {d.get('gate')}")
            else:
                w(f"  rank {d.get('rank')} phase {d.get('phase')} "
                  f"{d.get('ms')} ms vs median {d.get('median_ms')} ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", required=True,
                    help="HOROVOD_FLIGHT_RECORDER_DIR of the dead run")
    ap.add_argument("--json", help="also write the report dict here")
    args = ap.parse_args()
    if not os.path.isdir(args.dir):
        ap.error(f"no such directory: {args.dir}")
    report = build_report(args.dir)
    if report["dumps"] == 0:
        print(f"no parseable flight dumps in {args.dir}", file=sys.stderr)
        for c in report["corrupt"]:
            print(f"  CORRUPT {c['path']}: {c['error']}", file=sys.stderr)
        return 2
    print_report(report)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
