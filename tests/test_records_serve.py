"""Tenants whose records the service holds: ``register_tenant(records=)``,
``ReleaseRequest(records=)`` and the device-resident ``RecordStore``.

A records tenant's release must be the release a tables tenant gets from
the exact tables of the same records: the same measurements and tables, bit
for bit, whether served alone or fused with other tenants.  Its rows are
checked before the charge, appended in submission order, and counted on the
device by a program that compiles once per buffer shape.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Domain, all_kway, select
from repro.data.tabular import marginals_from_records
from repro.engine import sharded
from repro.engine.sharded import RecordStore
from repro.serve import BudgetLedger, ReleaseRequest, ReleaseServer

DOM = Domain.create([4, 3, 5, 2, 3])
BATCH = 24


def _records(seed, n):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, s, n) for s in DOM.sizes], axis=1)


@pytest.fixture(scope="module")
def plan():
    return select(all_kway(DOM, 2, include_lower=True), pcost_budget=1.0)


def _server(tmp_path, max_batch):
    ledger = BudgetLedger(os.path.join(str(tmp_path), "ledger.jsonl"),
                          fsync=False)
    return ReleaseServer(ledger, max_batch=max_batch).start()


def _bincounts(cliques, recs):
    """The plan closure's tables of ``recs``: numpy's bincount per clique."""
    out = []
    for c in cliques:
        if not c:
            out.append(np.array([recs.shape[0]]))
            continue
        code = np.zeros(recs.shape[0], np.int64)
        for a in c:
            code = code * DOM.sizes[a] + recs[:, a]
        out.append(np.bincount(code, minlength=DOM.n_cells(c)))
    return out


@pytest.mark.parametrize("max_batch", [1, 4])
def test_records_tenant_releases_bit_equal_tables_tenant(plan, tmp_path,
                                                         max_batch):
    """Two records tenants beside two tables tenants given the same base
    records, fresh batches and seeds.  With ``max_batch`` 4 each drain of
    four is one fused batch, and the last holds two releases of each
    records tenant, counted in submission order."""
    base = {t: _records(10 + t, 700) for t in range(2)}
    fresh = {(t, r): _records(100 * t + r, BATCH)
             for t in range(2) for r in range(4)}
    srv = _server(tmp_path, max_batch)
    try:
        for t in range(2):
            srv.register_tenant(f"rec{t}", plan, rho=100.0, records=base[t])
            srv.register_tenant(f"tab{t}", plan, rho=100.0)
        held = {t: base[t] for t in range(2)}
        for drain in ([(0, 0), (1, 0)], [(0, 1), (1, 1)],
                      [(0, 2), (0, 3)]):
            srv.pause()
            futs = []
            for t, r in drain:
                held[t] = np.concatenate([held[t], fresh[t, r]])
                seed = 1000 * t + r
                futs.append(srv.submit(ReleaseRequest(
                    tenant=f"rec{t}", records=fresh[t, r], seed=seed)))
                tables = marginals_from_records(DOM, plan.cliques, held[t])
                futs.append(srv.submit(ReleaseRequest(
                    tenant=f"tab{t}", marginals=tables, seed=seed)))
            srv.resume()
            res = [f.result(300) for f in futs]
            for rec, tab in zip(res[::2], res[1::2]):
                assert rec.batched == tab.batched == (max_batch > 1)
                assert set(rec.measurements) == set(tab.measurements)
                for c, m in rec.measurements.items():
                    assert np.array_equal(m.omega, tab.measurements[c].omega)
                    assert m.sigma2 == tab.measurements[c].sigma2
                assert set(rec.tables) == set(tab.tables)
                for c in rec.tables:
                    assert np.array_equal(rec.tables[c], tab.tables[c]), c
        assert srv.ledger.spent("rec0") == srv.ledger.spent("tab0") > 0
        resident = srv.metrics.sample_value("repro_records_resident_rows",
                                            tenant="rec0")
        assert resident == 700 + 4 * BATCH
        scanned = srv.metrics.sample_value(
            "repro_records_scanned_rows_total", tenant="rec0")
        assert scanned == sum(700 + k * BATCH for k in range(1, 5))
    finally:
        srv.stop()
        srv.ledger.close()


def test_store_tables_equal_bincount(plan, monkeypatch):
    """Appends of several sizes (empty, one row, a partial block, a whole
    one) into a store whose buffer is whole chunks of 32 rows (a one-hot
    block of the 17 attribute values), with rows of headroom past the
    records, until the buffer fills and grows."""
    monkeypatch.setattr(sharded, "_ONE_HOT_ELEMS", 32 * 17)
    monkeypatch.setattr(sharded, "HEADROOM_ROWS", 20)
    store = RecordStore(DOM, plan.cliques, _records(0, 70))
    assert store.capacity == 96                  # 90 rows, in chunks of 32
    held = _records(0, 70)
    capacities = set()
    for k, size in enumerate([0, 1, 13, 16, 5, 32, 9, 40]):
        rows = _records(50 + k, size)
        store.append(store.check_batch(rows, jnp.float32))
        held = np.concatenate([held, rows])
        assert store.n == held.shape[0] <= store.capacity
        capacities.add(store.capacity)
        got = store.marginals(jnp.float32)
        for c, g, want in zip(plan.cliques, got,
                              _bincounts(plan.cliques, held)):
            assert g.dtype == jnp.float32
            assert np.array_equal(np.asarray(g), want), (k, c)
    assert min(capacities) == 96 and max(capacities) > 96   # it filled


def test_growing_within_headroom_compiles_nothing(plan):
    store = RecordStore(DOM, plan.cliques, _records(1, 500))
    store.append(store.check_batch(_records(2, BATCH), jnp.float32))
    jax.block_until_ready(store.marginals(jnp.float32))
    capacity = store.capacity
    compiles = []

    def on_event(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for r in range(5):
            store.append(store.check_batch(_records(3 + r, BATCH),
                                           jnp.float32))
            jax.block_until_ready(store.marginals(jnp.float32))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert store.capacity == capacity and store.n == 500 + 6 * BATCH
    assert compiles == []


@pytest.mark.parametrize("tenant,payload,match", [
    ("rec", {"records": np.full((4, 5), 0)[:, :4]}, "array, got shape"),
    ("rec", {"records": np.zeros(5, np.int64)}, "array, got shape"),
    ("rec", {"records": np.zeros((4, 5), np.float32)}, "integers"),
    ("rec", {"records": np.array([[0, 0, 0, 2, 0]])}, "outside"),
    ("rec", {"records": np.array([[0, -1, 0, 0, 0]])}, "outside"),
    ("rec", {"marginals": {(): np.ones(1)}}, "not marginals="),
    ("tab", {"records": np.zeros((4, 5), np.int64)}, "not records="),
])
def test_bad_rows_or_wrong_payload_fail_before_the_charge(plan, tmp_path,
                                                          tenant, payload,
                                                          match):
    srv = _server(tmp_path, 4)
    try:
        srv.register_tenant("rec", plan, rho=100.0, records=_records(5, 300))
        srv.register_tenant("tab", plan, rho=100.0)
        journal = srv.ledger.path
        before = open(journal, "rb").read()
        with pytest.raises(ValueError, match=match):
            srv.request_sync(ReleaseRequest(tenant=tenant, **payload))
        assert open(journal, "rb").read() == before
        assert srv.ledger.spent(tenant) == 0.0
        # The tenant's records are as registered, and it still serves.
        r = srv.request_sync(ReleaseRequest(tenant="rec",
                                            records=_records(6, 8)))
        assert set(r.tables) == set(plan.workload.cliques)
        assert srv.metrics.sample_value("repro_records_resident_rows",
                                        tenant="rec") == 308
    finally:
        srv.stop()
        srv.ledger.close()


def test_batch_past_exact_counts_is_refused(plan):
    """float16 counts are exact to 2048: a store of 2000 rows refuses 64
    more, before anything is appended."""
    store = RecordStore(DOM, plan.cliques, _records(7, 2000))
    store.check_batch(_records(8, 48), jnp.float16)
    with pytest.raises(ValueError, match="not exact"):
        store.check_batch(_records(8, 64), jnp.float16)
    assert store.n == 2000


def test_records_spans(plan, tmp_path):
    """Traced, a records release records its ingest, scan and fetch under
    the request, with their sizes; the client's request_id names the
    charge in the journal."""
    from repro.obs import TRACER
    TRACER.enable()
    TRACER.drain()
    try:
        srv = _server(tmp_path, 1)
        try:
            srv.register_tenant("rec", plan, rho=100.0,
                                records=_records(9, 400))
            srv.request_sync(ReleaseRequest(tenant="rec", request_id="r-0",
                                            records=_records(10, BATCH)))
        finally:
            srv.stop()
            srv.ledger.close()
        spans = TRACER.drain()
    finally:
        TRACER.disable()
    by_id = {s.span_id: s for s in spans}
    named = {s.name: s for s in spans}
    for name in ("records.ingest", "records.scan", "records.fetch"):
        assert by_id[named[name].parent_id].name == "serve.request"
    cells = sum(DOM.n_cells(c) for c in plan.cliques)
    assert named["records.ingest"].attrs == {"rows": BATCH,
                                             "bytes": BATCH * 5 * 4}
    # Every clique but the empty one is counted by the Gram product.
    assert () in plan.cliques
    assert named["records.scan"].attrs == {
        "rows": 400 + BATCH, "cliques": len(plan.cliques),
        "product_cliques": len(plan.cliques) - 1, "cells": cells}
    assert named["records.fetch"].attrs["bytes"] == 4 * cells
    with open(os.path.join(str(tmp_path), "ledger.jsonl")) as fh:
        charges = [json.loads(line) for line in fh if '"charge"' in line]
    assert [c["request_id"] for c in charges] == ["r-0"]
