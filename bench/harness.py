"""One run of one cell: set-up, a measured window, the check, one result line.

A cell is found by name: its entry in ``BENCHMARK.json`` names a
configuration and a traffic mix; the configuration is the file the entry of
``configs`` gives (``bench/configs/<config>.json``), and its
``deployment_kind`` names ``bench/kinds/<kind>.py``, which builds the plan,
the tenants, each request and the check (the protocol is in
``bench/kinds/__init__.py``); the mix is ``bench/mixes/<traffic>.json``, and
each per-layer metric is read by ``bench/metrics/<metric>.py``.  A new cell,
kind or metric is new files and entries; this file does not change.

The window drives ``ReleaseServer.submit`` -> the resolved future in this
process, with the durable ledger, from closed-loop client threads; latency is
taken on the client's side.  Nothing compiles in it: set-up ends with one
batch of exactly the window's shapes.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import traffic
from bench.check import judge

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
RESULT_TIMEOUT_S = 600.0


class CellError(Exception):
    """The cell cannot run here: unknown name or kind, missing files or no
    chip."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    kind: type          # bench/kinds/<deployment_kind>.py's Deployment
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, mix,
                deployment_kind(config, w["config"], root),
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def deployment_kind(config: dict, config_name: str, root: Path = ROOT) -> type:
    """``bench/kinds/<deployment_kind>.py``'s ``Deployment``; no default."""
    kind = config.get("deployment_kind")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise CellError(f"configuration {config_name!r} names no "
                        f"deployment_kind (got {kind!r})")
    path = root / "bench" / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise CellError(f"configuration {config_name!r}: no deployment kind "
                        f"{kind!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Deployment


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Counts XLA backend compiles and their seconds (jax.monitoring)."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.count = 0
        self.log: List[float] = []      # monotonic end time of each compile
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1
            self.log.append(time.monotonic())


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class Loop:
    """Closed-loop clients: each keeps one request outstanding."""

    srv: object
    deployment: object
    names: List[str]
    seconds: float
    sample_k: int
    rng: object
    lock: threading.Lock = field(default_factory=threading.Lock)
    done: List[tuple] = field(default_factory=list)      # guarded-by: lock
    submitted: Counter = field(default_factory=Counter)  # guarded-by: lock
    failures: List[str] = field(default_factory=list)    # guarded-by: lock
    sample: List[tuple] = field(default_factory=list)    # guarded-by: lock
    n_in_window: int = 0                                 # guarded-by: lock
    t_open: Optional[float] = None                       # guarded-by: lock
    t_close: Optional[float] = None                      # guarded-by: lock
    arrived: int = 0                                     # guarded-by: lock
    all_arrived: threading.Event = field(default_factory=threading.Event)
    opened: threading.Event = field(default_factory=threading.Event)
    stop: threading.Event = field(default_factory=threading.Event)
    n_clients: int = 0

    def client(self, t: int) -> None:
        name = self.names[t]
        first = True
        while not self.stop.is_set():
            r, req, item = self.deployment.next_request(t)
            t0 = time.monotonic()
            fut = self.srv.submit(req)
            with self.lock:
                self.submitted[name] += 1
                if first:
                    self.arrived += 1
                    if self.arrived == self.n_clients:
                        self.all_arrived.set()
            first = False
            try:
                res = fut.result(timeout=RESULT_TIMEOUT_S)
            except Exception as exc:     # noqa: BLE001 - counted, not fatal
                with self.lock:
                    self.failures.append(f"{name} release {r}: {exc!r}")
                continue
            self._complete(t, r, item, res, t0, time.monotonic())

    def _complete(self, t, r, item, res, t0, t1) -> None:
        with self.lock:
            self.done.append((t0, t1, t, r))
            if self.t_open is None:
                self.t_open, self.t_close = t1, t1 + self.seconds
                self.opened.set()
                return
            if not self.t_open < t1 <= self.t_close:
                return
            # Reservoir sample, drawn from the seed, of the window's releases.
            self.n_in_window += 1
            kept = (t, r, item, res)
            if len(self.sample) < self.sample_k:
                self.sample.append(kept)
            else:
                j = int(self.rng.integers(0, self.n_in_window))
                if j < self.sample_k:
                    self.sample[j] = kept

    def completions_since(self, t: float) -> int:
        with self.lock:
            return sum(1 for _t0, t1, _t, _r in self.done if t1 > t)


def _profile(loop: Loop, mix: dict, workdir: str) -> dict:
    """Profile whole batches at the start of the window; returns the trace
    file and the host instants that bound it."""
    import glob

    import jax

    from bench.trace_reduce import SYNC
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    pdir = os.path.join(workdir, "profile")
    t0 = time.monotonic()
    jax.profiler.start_trace(pdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(SYNC):
        sync_mono = time.monotonic()
    # (N + 1) batches of completions from a point inside a batch leave N
    # whole batches between the drains that bound them.
    target = (int(mix["profile_batches"]) + 1) * int(mix["max_batch"])
    while (loop.completions_since(t0) < target + 1
           and time.monotonic() < loop.t_close):
        time.sleep(0.01)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(pdir, "**", "*.xplane.pb"), recursive=True)
    return {"file": files[0] if files else None, "t0": t0, "t1": t1,
            "sync_mono": sync_mono}


def _read_spans(path: str) -> List[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _quantile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if xs else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             workdir: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result object (printed by main)."""
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise CellError(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                        f"JAX sees {len(devices)} {devices[0].platform} "
                        f"device(s)")
    from repro.obs import TRACER
    from repro.runtime import enable_compile_cache
    from repro.serve import BudgetLedger, ReleaseServer

    enable_compile_cache()
    clock = CompileClock()
    cfg, mix = cell.config, cell.mix
    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bench-")

    # ---- set-up: the kind's inputs, server, tenants, one warm batch -------
    dep = cell.kind(cell, seed, devices[:cell.chips])
    names = list(dep.tenants)
    n_tenants = len(names)
    journal = os.path.join(workdir, "ledger.jsonl")
    ledger = BudgetLedger(journal, fsync=bool(cfg["ledger_fsync"]))
    srv = ReleaseServer(ledger, max_batch=int(mix["max_batch"]),
                        max_wait_ms=float(mix["max_wait_ms"]),
                        **dep.server_kwargs).start()
    dep.register(srv)
    loop = Loop(srv, dep, names, float(seconds), int(mix["check_sample"]),
                traffic.sample_rng(seed))
    srv.pause()
    warm = []
    for j in range(int(mix["max_batch"])):
        t = j % n_tenants
        _r, req, _item = dep.next_request(t)
        warm.append(srv.submit(req))
        loop.submitted[names[t]] += 1
    srv.resume()
    for f in warm:
        f.result(timeout=RESULT_TIMEOUT_S)
    del warm
    compiles_setup = (clock.count, clock.seconds)

    # ---- window -----------------------------------------------------------
    spans_path = os.path.join(workdir, "spans.jsonl")
    if trace:
        TRACER.enable(spans_path)
    outstanding = int(mix["outstanding_per_tenant"])
    threads = [threading.Thread(target=loop.client, args=(t,), daemon=True,
                                name=f"client-{t}-{k}")
               for t in range(n_tenants) for k in range(outstanding)]
    loop.n_clients = len(threads)
    srv.pause()            # every client's first request queues before any is
    for th in threads:     # served, so the first drains are full batches
        th.start()
    if not loop.all_arrived.wait(RESULT_TIMEOUT_S):
        raise CellError("clients did not all submit")
    srv.resume()
    if not loop.opened.wait(RESULT_TIMEOUT_S):
        raise CellError("no release completed")
    t_open, t_close = loop.t_open, loop.t_close
    prof = _profile(loop, mix, workdir) if trace else None
    time.sleep(max(0.0, t_close - time.monotonic()))
    loop.stop.set()
    for th in threads:
        th.join(RESULT_TIMEOUT_S)
        if th.is_alive():
            raise CellError(f"{th.name} did not finish")
    peak = peak_bytes(devices[:cell.chips])
    if trace:
        TRACER.disable()
        dropped = TRACER.stats()["dropped"]
    srv.stop()
    ledger.close()

    # ---- end-to-end numbers -------------------------------------------------
    in_window = [(t0, t1) for t0, t1, _t, _r in loop.done
                 if t_open < t1 <= t_close]
    t_last = max(t1 for _t0, t1 in in_window) if in_window else t_close
    lat = [t1 - t0 for t0, t1 in in_window]
    e2e = {"releases_per_s": len(in_window) / (t_last - t_open)
           if in_window else 0.0,
           "release_p50_s": _quantile(lat, 50),
           "release_p90_s": _quantile(lat, 90),
           "setup_s": t_open - t_start}

    # ---- per-layer numbers (traced run) ------------------------------------
    metrics: Dict[str, dict] = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from bench import layers
        if dropped:
            raise CellError(f"the tracer dropped {dropped} spans")
        ctx = layers.Context(
            cell=cell, spans=_read_spans(spans_path), t_open=t_open,
            t_close=t_close, compiles_in_window=sum(1 for t in clock.log
                                   if t_open <= t <= t_close),
            profile=prof, device_kind=devices[0].device_kind,
            **dep.layer_inputs(srv))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if ctx.sub is not None:
            device["busy_s"] = ctx.sub["busy_ns"] / 1e9
            device["window_s"] = (ctx.sub["hi"] - ctx.sub["lo"]) / 1e9
            breakdown = ctx.breakdown()
    else:
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}

    # ---- the check: after the window, with the program's state dropped ------
    sample = [(item, res) for _t, _r, item, res in
              sorted(loop.sample, key=lambda s: (s[0], s[1]))]
    del srv, loop.srv
    dep.drop()
    gc.collect()
    numbers = dep.check(sample, journal, loop.submitted)
    numbers["failed"] = float(len(loop.failures))
    ok, rows = judge(numbers, cfg["correct_limits"])
    if own_dir:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    out = {"correct": bool(ok), "attempted": int(sum(loop.submitted.values())),
           "failed": len(loop.failures), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["run"] = {"seed": seed, "seconds": seconds, "trace": int(trace),
                  "window_releases": len(in_window),
                  "checked_releases": len(sample),
                  "setup_compiles": compiles_setup[0],
                  "setup_compile_s": compiles_setup[1],
                  "window_compiles": sum(1 for t in clock.log
                                         if t_open <= t <= t_close),
                  "end_to_end": e2e, "readings": numbers,
                  "failures": loop.failures[:5]}
    out["checks"] = {r["name"]: {"value": _finite(r["value"]),
                                 "limit": r["limit"]} for r in rows}
    return out


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e300


def main(argv: List[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (CellError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # The compile cache lives at one fixed path inside the checkout, whatever
    # the environment says, so every run of this checkout finds it.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if importlib.util.find_spec("repro") is None:
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, row in out["checks"].items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    sys.stdout.flush()
    return 0
