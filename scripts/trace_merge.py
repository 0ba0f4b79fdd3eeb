#!/usr/bin/env python
"""Clock-align and merge per-process trace files into one timeline.

Subprocess workers (and any multi-process run) each export their own
``trace_<pid>.json`` with timestamps from their OWN ``time.perf_counter()``
epoch — loading two of them into Perfetto shows two unrelated time axes.
Each exporter embeds a ``clockSync`` anchor (one simultaneous
``(perf_counter, unix time)`` pair, microseconds); this script shifts every
file's events onto the shared wall-clock axis, rebases the merged timeline
to start near zero, stitches request-flow chains that CROSS files (a flow
id seen in several files gets exactly one global ``s`` at its earliest hop
and one ``f`` at its latest — per-file chain ends become steps), and writes
one merged Chrome-trace JSON::

    python scripts/trace_merge.py <trace-dir> [-o merged.json]
    python scripts/trace_merge.py a.json b.json -o merged.json

Tracks cannot collide across files (each file's events carry its pid), and
per-track event ORDER is preserved (a constant shift keeps intra-file order
under the stable sort), so the merged file passes the same
``scripts/trace_check.py`` gates as its inputs — including the flow checks.
Files missing ``clockSync`` (pre-merge traces) merge UNSHIFTED with a
warning: correct only when they came from one process.

Caveat: flow ids are pid-prefixed per-process counters — unique across
the processes of one run, but pids (hence ids) recycle across machine
lifetimes, so merge one run's files at a time or chains from different
runs sharing an id may stitch together.

Exit 0 on success; the merged path prints on stdout.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Tuple

#: sort rank at equal timestamps: close-before-open keeps adjacent spans
#: nesting, metadata first, flows after the B they bind to (the same tie
#: discipline the exporter uses)
_PH_RANK = {"M": -1, "E": 0, "B": 1}


def load(path: str) -> Tuple[dict, List[dict]]:
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError(f"{path}: missing traceEvents")
    return doc, events


def merge(paths: List[str]) -> dict:
    files: List[Tuple[str, dict, List[dict]]] = []
    for path in paths:
        doc, events = load(path)
        files.append((path, doc, events))
    # clock alignment: perf-based ts + (unix - perf) anchor = wall-clock us
    offsets: Dict[str, float] = {}
    for path, doc, _events in files:
        sync = doc.get("clockSync")
        if isinstance(sync, dict) and "perf_us" in sync and "unix_us" in sync:
            offsets[path] = float(sync["unix_us"]) - float(sync["perf_us"])
        else:
            offsets[path] = 0.0
            print(f"trace_merge: WARNING {os.path.basename(path)} has no "
                  "clockSync anchor; merging unshifted", file=sys.stderr)
    merged: List[Tuple[float, int, int, int, dict]] = []
    flow_events: Dict[object, List[int]] = {}   # id -> merged indices
    flow_files: Dict[object, set] = {}          # id -> source files
    idx = 0
    for fno, (path, _doc, events) in enumerate(files):
        off = offsets[path]
        for ev in events:
            if not isinstance(ev, dict):
                continue
            ev = dict(ev)
            if "ts" in ev and isinstance(ev["ts"], (int, float)):
                ev["ts"] = ev["ts"] + off
            ph = ev.get("ph")
            ts = ev.get("ts", float("-inf")) if ph != "M" else float("-inf")
            merged.append((ts, _PH_RANK.get(ph, 2), fno, idx, ev))
            if ph in ("s", "t", "f") and "id" in ev:
                flow_events.setdefault(ev["id"], []).append(len(merged) - 1)
                flow_files.setdefault(ev["id"], set()).add(fno)
            idx += 1
    # stitch cross-file chains: exactly one global s (earliest hop) and one
    # global f (latest); everything between becomes a step. Single-file
    # chains are already well-formed — leave them untouched.
    for fid, positions in flow_events.items():
        if len(flow_files.get(fid, ())) < 2:
            continue
        positions.sort(key=lambda p: (merged[p][0], merged[p][2],
                                      merged[p][3]))
        for k, p in enumerate(positions):
            ev = merged[p][4]
            if k == 0:
                ev["ph"] = "s"
                ev.pop("bp", None)
            elif k == len(positions) - 1:
                ev["ph"] = "f"
                ev["bp"] = "e"
            else:
                ev["ph"] = "t"
                ev.pop("bp", None)
    # stable order: ts, tie rank, then source order — intra-file relative
    # order of same-ts same-rank events is preserved (constant shift)
    merged.sort(key=lambda item: item[:4])
    events_out = [ev for _, _, _, _, ev in merged]
    # rebase near zero for readability (metadata events carry no ts)
    t0 = min((ev["ts"] for ev in events_out
              if isinstance(ev.get("ts"), (int, float))), default=0.0)
    for ev in events_out:
        if isinstance(ev.get("ts"), (int, float)):
            ev["ts"] = ev["ts"] - t0
    return {"traceEvents": events_out, "displayTimeUnit": "ms",
            "mergedFrom": [os.path.basename(p) for p in paths]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("targets", nargs="+",
                    help="a trace directory (merges every trace_<pid>.json "
                         "inside) or explicit trace JSON files")
    ap.add_argument("-o", "--output", default=None,
                    help="merged output path (default: trace_merged.json "
                         "next to the inputs)")
    args = ap.parse_args()

    if len(args.targets) == 1 and os.path.isdir(args.targets[0]):
        d = args.targets[0]
        paths = sorted(p for p in glob.glob(os.path.join(d, "trace_*.json"))
                       if os.path.basename(p) not in ("trace_crash.json",
                                                      "trace_merged.json"))
        out = args.output or os.path.join(d, "trace_merged.json")
    else:
        paths = list(args.targets)
        out = args.output or os.path.join(
            os.path.dirname(paths[0]) or ".", "trace_merged.json")
    if not paths:
        print(f"trace_merge: no trace_*.json under {args.targets[0]}")
        return 1
    try:
        doc = merge(paths)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"trace_merge: {e}")
        return 1
    with open(out, "w") as f:
        json.dump(doc, f)
    n = len(doc["traceEvents"])
    print(f"trace_merge: {len(paths)} file(s) -> {out} ({n} events)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
