#!/usr/bin/env python
"""Validate emitted span traces against the Chrome-trace schema.

Run over a trace file or a ``DSTPU_TRACE`` directory (every ``trace*.json``
inside)::

    python scripts/trace_check.py <file-or-dir> \
        [--require train serve ckpt train/offload] \
        [--require-flows serve/req] [--expect-crash]

Checks per file:

- the JSON parses and carries a ``traceEvents`` list;
- every event has the required keys (``ph``/``name``/``pid``/``tid``, plus
  ``ts`` for non-metadata events) with sane types;
- per (pid, tid) track: timestamps are MONOTONIC (non-decreasing) and every
  ``B`` has a matching ``E`` (same name, LIFO order) — i.e. spans nest;
- counter events carry numeric args;
- FLOW events (``ph`` s/t/f — the request-flow chains binding one request's
  hops across lanes/threads, docs/OBSERVABILITY.md): every flow id carries
  exactly one ``s`` and one ``f``, never backwards (``t_f < t_s``), with
  every step inside ``[t_s, t_f]``, and every flow event BINDS — its ts
  falls inside some span on its own track (a dangling binding renders as a
  floating arrowhead in Perfetto and means an exporter bug).

``--require <prefix>...`` additionally asserts (across ALL checked files
together) that each prefix matches at least one span, and that the matched
spans cover at least as many DISTINCT tracks as there are prefixes — the
"spans from N subsystems on distinct tracks" acceptance gate.

``--require-flows <prefix>...`` asserts each prefix is touched by at least
one CROSS-LANE flow chain: a flow id whose bound spans cover >= 2 distinct
tracks with a bound span (or its track) named under the prefix — e.g.
``--require-flows serve/req`` demands a request whose causal chain actually
crosses lanes (router placement -> prefill -> decode stints / migration).

``--expect-crash`` asserts a parseable ``trace_crash.json`` (the flight
recorder's dump) exists in the directory and contains at least one span.

Exit 0 on success; 1 with a per-file error listing otherwise. The serving,
router and health tests run it over the timelines they emit
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Set, Tuple

Track = Tuple[int, int]


def check_events(events: list, errors: List[str], src: str = ""):
    """Schema + B/E + monotonicity checks over one event list. Returns
    ``(tracks, spans, flows)``: the track-name map {(pid, tid): name}, the
    closed span intervals [(track, name, ts_b, ts_e)], and the flow events
    [(id, ph, track, ts)] for the flow checks."""
    tracks: Dict[Track, str] = {}
    spans: List[Tuple[Track, str, float, float]] = []
    flows: List[Tuple[object, str, Track, float]] = []
    if not isinstance(events, list):
        errors.append(f"{src}: traceEvents is not a list")
        return tracks, spans, flows
    stacks: Dict[Track, List[Tuple[str, float]]] = {}
    last_ts: Dict[Track, float] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"{src}: event #{i} is not an object")
            continue
        for key in ("ph", "name", "pid", "tid"):
            if key not in ev:
                errors.append(f"{src}: event #{i} missing required key '{key}'")
        ph = ev.get("ph")
        tid_key = (ev.get("pid", 0), ev.get("tid", 0))
        if ph == "M":
            if ev.get("name") == "thread_name":
                tracks[tid_key] = str(ev.get("args", {}).get("name", ""))
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            errors.append(f"{src}: event #{i} ({ev.get('name')!r}) has no "
                          "numeric 'ts'")
            continue
        prev = last_ts.get(tid_key)
        if prev is not None and ts < prev:
            errors.append(f"{src}: track {tid_key} ts not monotonic at event "
                          f"#{i} ({ev.get('name')!r}): {ts} < {prev}")
        last_ts[tid_key] = ts
        if ph == "B":
            stacks.setdefault(tid_key, []).append((str(ev.get("name")), ts))
        elif ph == "E":
            stack = stacks.setdefault(tid_key, [])
            if not stack:
                errors.append(f"{src}: track {tid_key} has 'E' "
                              f"({ev.get('name')!r}) with no open 'B'")
            elif stack[-1][0] != ev.get("name"):
                errors.append(f"{src}: track {tid_key} 'E' {ev.get('name')!r} "
                              f"does not match open 'B' {stack[-1][0]!r}")
            else:
                name, ts_b = stack.pop()
                spans.append((tid_key, name, ts_b, ts))
        elif ph == "C":
            args = ev.get("args", {})
            if not args or not all(isinstance(v, (int, float))
                                   for v in args.values()):
                errors.append(f"{src}: counter #{i} ({ev.get('name')!r}) "
                              "lacks numeric args")
        elif ph in ("s", "t", "f"):
            if "id" not in ev:
                errors.append(f"{src}: flow event #{i} ({ph!r}) has no 'id'")
            else:
                flows.append((ev["id"], ph, tid_key, float(ts)))
        elif ph not in ("i", "X"):
            errors.append(f"{src}: event #{i} has unknown phase {ph!r}")
    for tid_key, stack in stacks.items():
        if stack:
            errors.append(f"{src}: track {tid_key} left unmatched 'B' events: "
                          f"{[n for n, _ in stack]}")
    return tracks, spans, flows


def check_flows(flows, spans, tracks, errors: List[str], src: str = ""):
    """Flow-chain validation over one file. Returns ``{flow id: (bound
    track keys, bound span/track names)}`` for the --require-flows gate."""
    by_track: Dict[Track, List[Tuple[float, float, str]]] = {}
    for tid_key, name, b, e in spans:
        by_track.setdefault(tid_key, []).append((b, e, name))
    chains: Dict[object, List[Tuple[float, str, Track]]] = {}
    for fid, ph, tid_key, ts in flows:
        chains.setdefault(fid, []).append((ts, ph, tid_key))
    info: Dict[object, Tuple[Set[Track], Set[str]]] = {}
    for fid, evs in chains.items():
        phs = [p for _, p, _ in evs]
        n_s, n_f = phs.count("s"), phs.count("f")
        if n_s != 1 or n_f != 1:
            errors.append(f"{src}: flow id {fid} has {n_s} 's' and {n_f} "
                          "'f' events (need exactly one of each)")
            continue
        ts_s = next(ts for ts, p, _ in evs if p == "s")
        ts_f = next(ts for ts, p, _ in evs if p == "f")
        if ts_f < ts_s:
            errors.append(f"{src}: flow id {fid} is BACKWARDS: "
                          f"f at {ts_f} < s at {ts_s}")
            continue
        bad_steps = [ts for ts, p, _ in evs if p == "t"
                     and not ts_s <= ts <= ts_f]
        if bad_steps:
            errors.append(f"{src}: flow id {fid} has step events outside "
                          f"[{ts_s}, {ts_f}]: {bad_steps}")
        bound_tracks: Set[Track] = set()
        bound_names: Set[str] = set()
        for ts, ph, tid_key in evs:
            hit = [name for b, e, name in by_track.get(tid_key, ())
                   if b <= ts <= e]
            if not hit:
                errors.append(f"{src}: flow id {fid} '{ph}' at {ts} on track "
                              f"{tid_key} binds to no span (dangling)")
                continue
            bound_tracks.add(tid_key)
            bound_names.update(hit)
            bound_names.add(tracks.get(tid_key, ""))
        info[fid] = (bound_tracks, bound_names)
    return info


def check_file(path: str, errors: List[str]):
    """Returns (events, tracks, spans, flow_info) after recording errors."""
    src = os.path.basename(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        errors.append(f"{src}: unreadable/unparseable: {e}")
        return [], {}, [], {}
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        errors.append(f"{src}: missing top-level 'traceEvents'")
        return [], {}, [], {}
    events = doc["traceEvents"]
    tracks, spans, flows = check_events(events, errors, src=src)
    flow_info = check_flows(flows, spans, tracks, errors, src=src)
    return events, tracks, spans, flow_info


def span_names_by_track(events: list) -> Dict[Track, Set[str]]:
    out: Dict[Track, Set[str]] = {}
    for ev in events:
        if isinstance(ev, dict) and ev.get("ph") in ("B", "X"):
            key = (ev.get("pid", 0), ev.get("tid", 0))
            out.setdefault(key, set()).add(str(ev.get("name")))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("target", help="a trace JSON file or a directory of them")
    ap.add_argument("--require", nargs="*", default=[],
                    help="span-name/track prefixes that must each be present, "
                         "on at least as many distinct tracks as prefixes")
    ap.add_argument("--require-flows", nargs="*", default=[],
                    help="prefixes that must each be touched by a CROSS-LANE "
                         "flow chain (>= 2 distinct bound tracks)")
    ap.add_argument("--expect-crash", action="store_true",
                    help="require a parseable trace_crash.json in the dir")
    ap.add_argument("--min-spans", type=int, default=1,
                    help="minimum total spans across the checked files")
    args = ap.parse_args()

    if os.path.isdir(args.target):
        paths = sorted(glob.glob(os.path.join(args.target, "trace*.json")))
        crash = os.path.join(args.target, "trace_crash.json")
    else:
        paths = [args.target]
        crash = os.path.join(os.path.dirname(args.target) or ".",
                             "trace_crash.json")
    if not paths:
        print(f"trace_check: no trace*.json under {args.target}")
        return 1

    errors: List[str] = []
    total_spans = 0
    total_flows = 0
    # (file, pid, tid) -> set of span names; track names per the same key
    span_map: Dict[Tuple[str, int, int], Set[str]] = {}
    track_names: Dict[Tuple[str, int, int], str] = {}
    flow_infos: List[Tuple[Set[Track], Set[str]]] = []
    for path in paths:
        events, tracks, _spans, flow_info = check_file(path, errors)
        by_track = span_names_by_track(events)
        for (pid, tid), names in by_track.items():
            key = (path, pid, tid)
            span_map[key] = names
            track_names[key] = tracks.get((pid, tid), "")
            total_spans += len(names)
        flow_infos.extend(flow_info.values())
        total_flows += len(flow_info)

    if total_spans < args.min_spans:
        errors.append(f"only {total_spans} distinct span names across "
                      f"{len(paths)} file(s); expected >= {args.min_spans}")

    if args.require:
        matched_tracks: Set[Tuple[str, int, int]] = set()
        for prefix in args.require:
            hits = {key for key, names in span_map.items()
                    if any(n.startswith(prefix) for n in names)
                    or track_names.get(key, "").startswith(prefix)}
            if not hits:
                errors.append(f"required subsystem prefix {prefix!r} matched "
                              "no spans in any checked trace")
            matched_tracks |= hits
        if len(matched_tracks) < len(args.require):
            errors.append(
                f"required subsystems span only {len(matched_tracks)} "
                f"distinct tracks; expected >= {len(args.require)}")

    for prefix in args.require_flows:
        if not any(len(tracks_) >= 2
                   and any(n.startswith(prefix) for n in names)
                   for tracks_, names in flow_infos):
            errors.append(f"--require-flows: no cross-lane flow chain "
                          f"(>= 2 bound tracks) touches prefix {prefix!r}")

    if args.expect_crash:
        if not os.path.exists(crash):
            errors.append(f"--expect-crash: {crash} does not exist")
        else:
            crash_errors: List[str] = []
            events, *_ = check_file(crash, crash_errors)
            n_spans = sum(1 for ev in events
                          if isinstance(ev, dict) and ev.get("ph") == "B")
            if crash_errors:
                errors.extend(crash_errors)
            elif n_spans == 0:
                errors.append(f"{os.path.basename(crash)}: flight recorder "
                              "dump contains no spans")

    if errors:
        for err in errors:
            print(f"trace_check: {err}")
        print(f"trace_check: FAIL ({len(errors)} error(s) across "
              f"{len(paths)} file(s))")
        return 1
    print(f"trace_check: OK — {len(paths)} file(s), {total_spans} distinct "
          f"span names, {len(span_map)} tracks, {total_flows} flow chains"
          + (", crash dump present" if args.expect_crash else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
