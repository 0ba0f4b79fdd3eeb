#!/usr/bin/env python
"""Per-request waterfall: where did ONE request's latency go?

Reads span traces (a ``DSTPU_TRACE`` directory, a single ``trace_*.json``,
or a ``trace_merge.py`` output), collects every span carrying a
``trace_id`` arg — the request-flow chain the serving stack stamps at
submit and threads through router placement, prefill, KV handoff, decode
stints, preemption/restore and failover migration — and renders the chain
as an ASCII waterfall plus a per-phase attribution summary (the offline
twin of ``RequestHandle.timeline()``; docs/OBSERVABILITY.md "SLO-miss
attribution")::

    python scripts/request_autopsy.py /tmp/run_traces --trace-id 1048577
    python scripts/request_autopsy.py /tmp/run_traces          # worst chain
    python scripts/request_autopsy.py "$DSTPU_TRACE" --smoke   # CI gate

With no ``--trace-id``, the WORST chain (largest submit-to-last-hop
window) is picked — on an SLO-investigation that is usually the request
you want. ``--list`` prints every chain's window instead. ``--smoke``
(run by ``tests/unit/test_trace.py``) asserts at least one multi-hop
chain exists in the traces and renders the worst one; exit 1 otherwise.

Timestamps are clock-aligned across files via the exporters' ``clockSync``
anchors (the same correction ``trace_merge.py`` applies), so a chain whose
hops span subprocess workers still renders as one causal timeline.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

BAR_WIDTH = 44


class Hop:
    __slots__ = ("name", "track", "t0", "t1", "args")

    def __init__(self, name, track, t0, t1, args):
        self.name = name
        self.track = track
        self.t0 = t0
        self.t1 = t1
        self.args = args


def collect(paths: List[str]) -> Dict[object, List[Hop]]:
    """{trace_id: [hops]} across the given files, clock-aligned."""
    chains: Dict[object, List[Hop]] = {}
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"request_autopsy: skipping {path}: {e}", file=sys.stderr)
            continue
        events = doc.get("traceEvents") or []
        sync = doc.get("clockSync") or {}
        off = (float(sync["unix_us"]) - float(sync["perf_us"])
               if "unix_us" in sync and "perf_us" in sync else 0.0)
        tracks: Dict[Tuple[int, int], str] = {}
        stacks: Dict[Tuple[int, int], list] = {}
        for ev in events:
            if not isinstance(ev, dict):
                continue
            ph = ev.get("ph")
            key = (ev.get("pid", 0), ev.get("tid", 0))
            if ph == "M":
                if ev.get("name") == "thread_name":
                    tracks[key] = str(ev.get("args", {}).get("name", ""))
            elif ph == "B":
                stacks.setdefault(key, []).append(ev)
            elif ph == "E":
                stack = stacks.get(key)
                if not stack:
                    continue
                b = stack.pop()
                args = b.get("args") or {}
                tid_val = args.get("trace_id")
                if tid_val is None:
                    continue
                chains.setdefault(tid_val, []).append(
                    Hop(str(b.get("name")), tracks.get(key, str(key)),
                        float(b.get("ts", 0.0)) + off,
                        float(ev.get("ts", 0.0)) + off, args))
    for hops in chains.values():
        hops.sort(key=lambda h: (h.t0, h.t1))
    return chains


def render(trace_id, hops: List[Hop]) -> str:
    t_min = min(h.t0 for h in hops)
    t_max = max(h.t1 for h in hops)
    window = max(t_max - t_min, 1e-9)
    cls = next((h.args.get("cls") for h in hops if "cls" in h.args), None)
    lines = [f"request autopsy — trace_id {trace_id}"
             + (f" (class {cls})" if cls else ""),
             f"window: {window / 1e3:.2f} ms over {len(hops)} hops "
             f"on {len({h.track for h in hops})} lanes", ""]
    name_w = max(len(h.name) for h in hops)
    track_w = max(len(h.track) for h in hops)
    for h in hops:
        lo = int(BAR_WIDTH * (h.t0 - t_min) / window)
        hi = max(lo + 1, int(round(BAR_WIDTH * (h.t1 - t_min) / window)))
        bar = " " * lo + "#" * (hi - lo)
        lines.append(f"  {h.name:<{name_w}}  {h.track:<{track_w}}  "
                     f"{(h.t0 - t_min) / 1e3:9.2f} ms  "
                     f"{(h.t1 - h.t0) / 1e3:9.2f} ms  |{bar:<{BAR_WIDTH}}|")
    # per-phase attribution: serve/req/* stints summed by phase (the
    # offline ledger view; cross-lane control spans are listed, not
    # summed). serve/req/handoff is import WORK nested inside its
    # enclosing handoff_wait/migration stint on the same lane — summing
    # it too would double-count the overlap, so it stays a hop row only.
    phases: Dict[str, float] = {}
    for h in hops:
        if h.name.startswith("serve/req/") and h.name != "serve/req/handoff":
            phases[h.name[len("serve/req/"):]] = \
                phases.get(h.name[len("serve/req/"):], 0.0) + (h.t1 - h.t0)
    if phases:
        total = sum(phases.values())
        lines.append("")
        lines.append(f"  phase attribution ({total / 1e3:.2f} ms attributed):")
        for phase, us in sorted(phases.items(), key=lambda kv: -kv[1]):
            lines.append(f"    {phase:<14} {us / 1e3:9.2f} ms  "
                         f"{100.0 * us / total:5.1f}%")
        dom = max(phases, key=lambda p: phases[p])
        lines.append(f"    dominant phase: {dom}")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("target", help="trace JSON file or DSTPU_TRACE directory")
    ap.add_argument("--trace-id", type=int, default=None,
                    help="autopsy this request (default: the worst chain)")
    ap.add_argument("--list", action="store_true",
                    help="list every chain's window instead of rendering one")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: require >= 1 multi-hop chain, render the "
                         "worst")
    args = ap.parse_args()

    if os.path.isdir(args.target):
        # skip the merged file (its events duplicate the inputs) and the
        # crash dump (a mid-run snapshot of the same rings the final
        # trace_<pid>.json re-exports — including it double-counts stints)
        paths = sorted(
            p for p in glob.glob(os.path.join(args.target, "trace*.json"))
            if os.path.basename(p) not in ("trace_merged.json",
                                           "trace_crash.json"))
    else:
        paths = [args.target]
    if not paths:
        print(f"request_autopsy: no trace*.json under {args.target}")
        return 1
    chains = collect(paths)
    if args.trace_id is not None:
        hops = chains.get(args.trace_id)
        if not hops:
            print(f"request_autopsy: no spans carry trace_id "
                  f"{args.trace_id} (known: {sorted(chains)[:20]}...)")
            return 1
        print(render(args.trace_id, hops))
        return 0
    if not chains:
        print("request_autopsy: no request chains (spans with a trace_id "
              "arg) in the given traces")
        return 1
    windows = {tid: max(h.t1 for h in hops) - min(h.t0 for h in hops)
               for tid, hops in chains.items()}
    if args.list:
        for tid in sorted(windows, key=lambda t: -windows[t]):
            hops = chains[tid]
            print(f"  trace_id {tid}: {windows[tid] / 1e3:9.2f} ms, "
                  f"{len(hops)} hops, "
                  f"{len({h.track for h in hops})} lanes")
        return 0
    if args.smoke:
        multi = {tid for tid, hops in chains.items() if len(hops) >= 2}
        if not multi:
            print("request_autopsy: SMOKE FAIL — no multi-hop request "
                  "chain in the traces")
            return 1
        worst = max(multi, key=lambda t: windows[t])
        print(render(worst, chains[worst]))
        print(f"\nrequest_autopsy: smoke OK — {len(chains)} chains, "
              f"{len(multi)} multi-hop")
        return 0
    worst = max(windows, key=lambda t: windows[t])
    print(render(worst, chains[worst]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
