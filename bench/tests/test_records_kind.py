"""The ``records`` kind (``adult2.fused4``): a CPU rehearsal at a tiny size,
the faults its check must catch, and the work model of
``records_scan_roofline``."""
import copy
import importlib.util
import time

import numpy as np
import pytest

from bench import harness, layers

CELL = "adult2.fused4"


def tiny(**mix):
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell.config.update(sizes=[4, 3, 5, 2, 3, 2], records_per_tenant=500,
                       records_per_release=8)
    cell.mix.update(mix)
    return cell


def run(cell, tmp_path, trace=False, seconds=1.5, seed=2 ** 33 + 7):
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.monotonic(), require_tpu=False,
                            workdir=str(tmp_path))


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal(trace, tmp_path):
    cell = tiny()
    assert cell.config["deployment_kind"] == "records"
    out = run(cell, tmp_path, trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["run"]["window_releases"] > 0
    if trace:
        # Off the chip there is no device trace to read a roofline from.
        assert set(out["metrics"]) == {"records_scan_s"}
        assert out["metrics"]["records_scan_s"]["value"] > 0
    else:
        assert set(out["metrics"]) == {"releases_per_s", "setup_s"}


def _service_skips_a_batch(monkeypatch):
    """The service drops the sixth batch it is handed (the warm batch
    takes four)."""
    from repro.engine.sharded import RecordStore
    real = RecordStore.append
    calls = []

    def append(self, rows):
        calls.append(len(rows))
        if len(calls) != 6:
            real(self, rows)

    monkeypatch.setattr(RecordStore, "append", append)


def _client_omits_a_batch(monkeypatch):
    """One request goes out without its fresh rows, while the reference
    counts them."""
    cls = harness.deployment_kind({"deployment_kind": "records"}, "adult2")
    real = cls.next_request

    def next_request(self, t):
        r, req, item = real(self, t)
        if (t, r) == (1, 3):
            req.records = req.records[:0]
        return r, req, item

    monkeypatch.setattr(cls, "next_request", next_request)
    return cls


@pytest.mark.parametrize("fault", [_service_skips_a_batch,
                                   _client_omits_a_batch])
def test_a_left_out_batch_is_not_correct(fault, monkeypatch, tmp_path):
    cell = tiny(check_sample=10 ** 6)     # every release of the window
    kind = fault(monkeypatch)
    if kind is not None:
        cell.kind = kind
    out = run(cell, tmp_path, seconds=1.0, seed=9)
    assert not out["correct"], out["checks"]
    assert out["checks"]["table_sd"]["value"] > 1.0


def _reader():
    path = harness.BENCH / "metrics" / "records_scan_roofline.py"
    spec = importlib.util.spec_from_file_location("records_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_least_bytes_by_hand():
    m = _reader()
    # Adult at ACS scale: 1,664,500 rows x 14 one-byte values, and
    # 148,726 float32 cells.
    adult = [100, 100, 100, 99, 85, 42, 16, 15, 9, 7, 6, 5, 2, 2]
    assert m.least_bytes(1_664_500, adult, 148_726) == \
        23_303_000 + 594_904
    # An attribute of 300 values takes two bytes: 10 rows x 3 values x 2 B,
    # and 7 cells.
    assert m.least_bytes(10, [300, 4, 2], 7) == 60 + 28
    assert m.value_bytes([256]) == 1 and m.value_bytes([257]) == 2


def test_roofline_reader_on_spans():
    """Two scans inside the traced sub-window, one outside: each span's
    least time over the device's busy time inside it."""
    m = _reader()
    cell = tiny()

    def span(t0, t1, rows):
        return {"trace": "t", "span": f"s{t0}", "parent": None,
                "name": "records.scan", "t0": t0, "t1": t1,
                "attrs": {"rows": rows, "cliques": 3, "cells": 100}}

    spans = [span(1.0, 2.0, 1000), span(3.0, 4.0, 2000),
             span(9.0, 9.5, 5000)]
    ctx = layers.Context(cell=cell, spans=spans, t_open=0.0, t_close=10.0,
                         compiles_in_window=0, plan_groups={},
                         chain_fused={}, profile=None, device_kind="cpu")
    ns = 1_000_000_000
    ctx.sub = {"lo": 0, "hi": 5 * ns, "busy_ns": 0,
               "host": [(int(s["t0"] * ns), int(s["t1"] * ns), s["name"])
                        for s in spans],
               # 0.25 s busy in the first scan (two overlapping ops), 0.5 s
               # in the second, an op between them that no scan covers.
               "ops": [(int(1.1 * ns), int(1.3 * ns), "a"),
                       (int(1.2 * ns), int(1.35 * ns), "b"),
                       (int(2.5 * ns), int(2.9 * ns), "c"),
                       (int(3.5 * ns), int(4.2 * ns), "d")],
               "chains": []}
    ctx.peaks = {"hbm_bytes_per_s": 1e6}
    least = (m.least_bytes(1000, cell.config["sizes"], 100)
             + m.least_bytes(2000, cell.config["sizes"], 100)) / 1e6
    assert m.read(ctx) == pytest.approx(100.0 * least / 0.75)
    ctx.sub["host"] = [h for h in ctx.sub["host"] if h[2] != "records.scan"]
    assert m.read(ctx) is None


def test_reference_tables_match_a_count(tmp_path):
    """The kind's reference tables of a release are the exact counts of
    the base records and the batches it names."""
    cell = tiny()
    dep = cell.kind(cell, 5, None)
    base = dep.layout.tables(dep.base_records(1)).astype(np.float64)
    got = dep.tables(1, [0, 2], base)
    recs = np.concatenate([dep.base_records(1), dep.streams[1].fresh_batch(0),
                           dep.streams[1].fresh_batch(2)])
    assert np.array_equal(got, dep.layout.tables(recs))
