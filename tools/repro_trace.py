#!/usr/bin/env python
"""repro-trace CLI — render JSONL span trees from the tracing subsystem.

Input is the sink written by ``REPRO_TRACE=/path`` or
``python -m repro.launch.serve --trace /path`` (one JSON span per line, see
src/repro/obs/trace.py for the schema).  Usage:

    python tools/repro_trace.py trace.jsonl              # waterfall per trace
    python tools/repro_trace.py trace.jsonl --list       # one line per trace
    python tools/repro_trace.py trace.jsonl --trace-id 8f3c0a...
    python tools/repro_trace.py trace.jsonl --kernels    # launches per chain
    python tools/repro_trace.py trace.jsonl --json       # machine-readable

The waterfall shows, for every request trace, the span tree (indent =
parent link) with a time bar scaled to the trace's wall clock, plus a
critical-path breakdown: how much of the root span went to queue wait,
ledger charge, fused measurement, release postprocessing, and synthesis,
with the time spent waiting on the device and copying back called out
separately.  See docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional

BAR_WIDTH = 40

# Span names that make up the serve critical path, in pipeline order.
# "device_wait" (every *.fetch span: the wait on a device program and the
# copy of its outputs to the host) is reported as a sub-bucket: the fetch
# spans are children of the measure and release spans, so their time is
# already inside those buckets — counting it in the sum would overshoot.
PHASES = (
    ("queue_wait", ("serve.queue_wait",)),
    ("charge", ("serve.charge",)),
    ("measure", ("serve.fuse", "engine.measure")),
    ("release", ("engine.reconstruct", "release.postprocess")),
    ("synthesize", ("serve.synthesize",)),
)


def load_spans(path: str) -> List[dict]:
    spans = []
    with open(path, encoding="utf-8") as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                print(f"warning: {path}:{i + 1}: unparseable line skipped",
                      file=sys.stderr)
                continue
            if "trace" in rec and "span" in rec:
                spans.append(rec)
    return spans


def group_traces(spans: List[dict]) -> Dict[str, List[dict]]:
    traces: Dict[str, List[dict]] = defaultdict(list)
    for s in spans:
        traces[s["trace"]].append(s)
    return traces


def find_root(spans: List[dict]) -> Optional[dict]:
    """The root is the span whose parent is absent from this trace."""
    ids = {s["span"] for s in spans}
    roots = [s for s in spans if not s.get("parent") or s["parent"] not in ids]
    if not roots:
        return None
    return min(roots, key=lambda s: s["t0"])


def children_index(spans: List[dict]) -> Dict[Optional[str], List[dict]]:
    kids: Dict[Optional[str], List[dict]] = defaultdict(list)
    ids = {s["span"] for s in spans}
    for s in spans:
        parent = s.get("parent")
        kids[parent if parent in ids else None].append(s)
    for v in kids.values():
        v.sort(key=lambda s: (s["t0"], s["t1"]))
    return kids


def _fmt_ms(us: float) -> str:
    return f"{us / 1000.0:8.3f}ms"


def _attr_str(attrs: dict) -> str:
    if not attrs:
        return ""
    parts = [f"{k}={v}" for k, v in attrs.items()]
    return " [" + " ".join(parts) + "]"


def render_waterfall(trace_id: str, spans: List[dict], out=sys.stdout) -> None:
    t_min = min(s["t0"] for s in spans)
    t_max = max(s["t1"] for s in spans)
    wall = max(t_max - t_min, 1e-12)
    kids = children_index(spans)
    root = find_root(spans)
    out.write(f"trace {trace_id}  ({len(spans)} spans, "
              f"{wall * 1e3:.3f}ms wall)\n")

    def bar(s: dict) -> str:
        lo = int((s["t0"] - t_min) / wall * BAR_WIDTH)
        hi = int((s["t1"] - t_min) / wall * BAR_WIDTH)
        hi = max(hi, lo + 1)
        return " " * lo + "#" * (hi - lo) + " " * (BAR_WIDTH - hi)

    def walk(s: dict, depth: int) -> None:
        name = "  " * depth + s["name"]
        out.write(f"  {name:<34} |{bar(s)}| {_fmt_ms(s['dur_us'])}"
                  f"{_attr_str(s.get('attrs') or {})}\n")
        for c in kids.get(s["span"], ()):
            walk(c, depth + 1)

    top = kids.get(None, [])
    if root is not None and root not in top:
        top = [root] + top
    for s in top:
        walk(s, 0)
    breakdown = critical_path(spans)
    if breakdown:
        out.write("  critical path: " + "  ".join(
            f"{k}={v / 1000.0:.3f}ms" for k, v in breakdown.items()) + "\n")
    out.write("\n")


def critical_path(spans: List[dict]) -> Dict[str, float]:
    """Phase breakdown of one trace in microseconds.

    Each bucket sums the spans listed in :data:`PHASES`; ``device_wait``
    reports the ``*.fetch`` time nested inside the measure and release
    buckets; ``other`` is the root duration not covered by any top-level
    bucket (scheduling, python glue).  Buckets with zero time are omitted.
    """
    root = find_root(spans)
    by_phase: Dict[str, float] = {}
    for phase, names in PHASES:
        t = sum(s["dur_us"] for s in spans if s["name"] in names)
        if t > 0:
            by_phase[phase] = t
    wait = sum(s["dur_us"] for s in spans if s["name"].endswith(".fetch"))
    if wait > 0:
        by_phase["device_wait"] = wait
    if root is not None:
        covered = sum(v for k, v in by_phase.items() if k != "device_wait")
        other = root["dur_us"] - covered
        if other > 0.05 * root["dur_us"]:
            by_phase["other"] = other
        by_phase["total"] = root["dur_us"]
    return by_phase


def kernel_table(spans: List[dict], out=sys.stdout) -> List[dict]:
    """Chain launches and the rows they launched, per chain label, over
    every trace.  ``kernel.chain`` spans are zero-length markers: all chains
    of a batch run in one program, so they carry counts, not durations."""
    groups: Dict[tuple, List[dict]] = defaultdict(list)
    for s in spans:
        if s["name"] != "kernel.chain":
            continue
        attrs = s.get("attrs") or {}
        groups[(str(attrs.get("chain", "?")),
                bool(attrs.get("fused", False)))].append(s)
    rows = []
    for (chain, fused), ss in sorted(groups.items()):
        per_launch = chain.split("/")[1][1:] if chain.count("/") == 2 else ""
        rows.append({
            "chain": chain, "fused": fused, "launches": len(ss),
            "rows": len(ss) * int(per_launch) if per_launch.isdigit()
            else None,
            "tune_source": (ss[0].get("attrs") or {}).get("tune_source"),
        })
    if out is not None:
        out.write(f"{'chain':<20} {'fused':>5} {'launches':>8} "
                  f"{'rows':>10}  tune\n")
        for r in rows:
            out.write(f"{r['chain']:<20} {str(r['fused']):>5} "
                      f"{r['launches']:>8} {str(r['rows']):>10}  "
                      f"{r['tune_source']}\n")
    return rows


def list_traces(traces: Dict[str, List[dict]], out=sys.stdout) -> List[dict]:
    rows = []
    for tid, spans in sorted(traces.items(),
                             key=lambda kv: min(s["t0"] for s in kv[1])):
        root = find_root(spans)
        attrs = (root.get("attrs") or {}) if root else {}
        rows.append({
            "trace": tid, "spans": len(spans),
            "root": root["name"] if root else "?",
            "dur_ms": (root["dur_us"] / 1000.0) if root else None,
            "tenant": attrs.get("tenant"), "outcome": attrs.get("outcome"),
        })
    if out is not None:
        out.write(f"{'trace':<18} {'spans':>5} {'root':<16} {'dur':>10} "
                  f"{'tenant':<12} outcome\n")
        for r in rows:
            dur = f"{r['dur_ms']:.3f}ms" if r["dur_ms"] is not None else "?"
            out.write(f"{r['trace']:<18} {r['spans']:>5} {r['root']:<16} "
                      f"{dur:>10} {str(r['tenant']):<12} {r['outcome']}\n")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render JSONL traces from the repro obs subsystem")
    ap.add_argument("path", help="JSONL trace file (REPRO_TRACE sink)")
    ap.add_argument("--trace-id", default=None,
                    help="render only this trace (prefix match)")
    ap.add_argument("--list", action="store_true",
                    help="one summary line per trace, no waterfalls")
    ap.add_argument("--kernels", action="store_true",
                    help="chain launches and rows per chain label only")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the report as JSON instead of text")
    args = ap.parse_args(argv)

    spans = load_spans(args.path)
    if not spans:
        print(f"no spans in {args.path}", file=sys.stderr)
        return 1
    traces = group_traces(spans)
    if args.trace_id:
        traces = {tid: ss for tid, ss in traces.items()
                  if tid.startswith(args.trace_id)}
        if not traces:
            print(f"no trace matching {args.trace_id!r}", file=sys.stderr)
            return 1

    if args.as_json:
        report = {
            "traces": list_traces(traces, out=None),
            "critical_path": {tid: critical_path(ss)
                              for tid, ss in traces.items()},
            "kernels": kernel_table(spans, out=None),
        }
        json.dump(report, sys.stdout, indent=2)
        sys.stdout.write("\n")
        return 0
    if args.kernels:
        kernel_table(spans)
        return 0
    if args.list:
        list_traces(traces)
        return 0
    for tid in sorted(traces,
                      key=lambda t: min(s["t0"] for s in traces[t])):
        render_waterfall(tid, traces[tid])
    if any(s["name"] == "kernel.chain" for s in spans):
        print("kernel launches:")
        kernel_table(spans)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:            # e.g. `repro_trace.py --json | head`
        import os
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
