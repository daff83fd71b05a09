"""Release server: cross-tenant batching, budget enforcement, warm pool.

The kernel-launch-counter test follows the PR-4 hot-path-flag style: patch
the chain-launch entry point the fused path uses and count invocations — two
same-signature tenants served in one batch must cost exactly as many chain
launches as one tenant alone.
"""
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from repro.core import Domain, all_kway, select
from repro.core.accountant import BudgetExhausted
from repro.core.mechanism import measure, pcost_of_plan
from repro.data.tabular import marginals_from_records, synthetic_records
from repro.engine import multi as multi_mod
from repro.engine.multi import can_fuse, measure_multi
from repro.serve import (BudgetLedger, EnginePool, ReleaseRequest,
                         ReleaseServer, start_stats_http)

DOM = Domain.create([5, 5, 5])          # uniform sizes -> 2 chain signatures


def _tenant_setup(n_tenants, n_records=2000):
    wk = all_kway(DOM, 2, include_lower=True)
    plans, margs = [], []
    for t in range(n_tenants):
        plan = select(wk, pcost_budget=1.0)
        plans.append(plan)
        recs = synthetic_records(DOM, n_records, seed=t)
        margs.append(marginals_from_records(DOM, plan.cliques, recs))
    return plans, margs


def _server(tmp_path, plans, rho=100.0, **kw):
    ledger = BudgetLedger(os.path.join(str(tmp_path), "ledger.jsonl"),
                          fsync=False)
    srv = ReleaseServer(ledger, **kw).start()
    for i, plan in enumerate(plans):
        srv.register_tenant(f"t{i}", plan, rho=rho)
    return srv


# ------------------------------------------------------------- measure_multi
def test_measure_multi_bit_exact_vs_per_request():
    plans, margs = _tenant_setup(3)
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    seq = [measure(p, m, k) for p, m, k in zip(plans, margs, keys)]
    fused = measure_multi(list(zip(plans, margs, keys)))
    for s, f in zip(seq, fused):
        assert set(s) == set(f)
        for c in s:
            assert np.array_equal(s[c].omega, f[c].omega), c
            assert s[c].sigma2 == f[c].sigma2


def test_measure_multi_rejects_unfusable_plans():
    from repro.core.plus import PlusSchema, select_plus
    dom = Domain.create([6, 4], kinds=["numeric", "categorical"])
    wk = all_kway(dom, 2, include_lower=True)
    schema = PlusSchema.create(dom, ["range", "identity"],
                               strategy_mode="hier")
    pp = select_plus(wk, schema, pcost_budget=1.0)
    assert not can_fuse(pp)
    recs = synthetic_records(dom, 500, seed=0)
    margs = marginals_from_records(dom, pp.cliques, recs)
    with pytest.raises(ValueError, match="plain marginal plans"):
        measure_multi([(pp, margs, jax.random.PRNGKey(0))])


def test_cross_tenant_batching_shares_chain_launches(tmp_path, monkeypatch):
    """Two same-signature tenants in one batch ride the SAME chain launches
    (kernel-launch counter): fused launches == launches for one tenant.

    A batch runs as one compiled program, so launches are counted while it
    is traced: the program cache is cleared before each counted batch."""
    calls = {"n": 0}
    real = multi_mod.kron_matvec_batched

    def counting(factors, x, dims):
        calls["n"] += 1
        return real(factors, x, dims)

    monkeypatch.setattr(multi_mod, "kron_matvec_batched", counting)

    plans, margs = _tenant_setup(2)
    keys = [jax.random.PRNGKey(7), jax.random.PRNGKey(8)]

    calls["n"] = 0
    multi_mod._flat_program.cache_clear()
    measure_multi([(plans[0], margs[0], keys[0])])
    solo_launches = calls["n"]
    assert solo_launches == 2            # signatures (5,) and (5,5)

    calls["n"] = 0
    multi_mod._flat_program.cache_clear()
    measure_multi(list(zip(plans, margs, keys)))
    assert calls["n"] == solo_launches   # second tenant rides along free

    # ... and through the server: one paused batch, two tenants, no extra
    # launches beyond the solo count.
    srv = _server(tmp_path, plans, max_batch=8, max_wait_ms=1.0)
    try:
        srv.pause()
        futs = [srv.submit(ReleaseRequest(tenant=f"t{i}", marginals=margs[i],
                                          seed=i))
                for i in range(2)]
        calls["n"] = 0
        multi_mod._flat_program.cache_clear()
        srv.resume()
        res = [f.result(120) for f in futs]
        assert calls["n"] == solo_launches
        assert all(r.batched for r in res)
        assert all(r.batch_size == 2 for r in res)
    finally:
        srv.stop()
        srv.ledger.close()


def test_server_sequential_and_batched_bit_identical(tmp_path):
    plans, margs = _tenant_setup(3)

    def run(max_batch):
        srv = _server(tmp_path.joinpath(f"b{max_batch}"), plans,
                      max_batch=max_batch)
        try:
            srv.pause()
            futs = [srv.submit(ReleaseRequest(tenant=f"t{i}",
                                              marginals=margs[i], seed=40 + i))
                    for i in range(3)]
            srv.resume()
            return [f.result(120) for f in futs]
        finally:
            srv.stop()
            srv.ledger.close()

    os.makedirs(str(tmp_path / "b1"), exist_ok=True)
    os.makedirs(str(tmp_path / "b8"), exist_ok=True)
    seq, bat = run(1), run(8)
    assert not any(r.batched for r in seq)
    for a, b in zip(seq, bat):
        assert set(a.tables) == set(b.tables)
        for c in a.tables:
            assert np.array_equal(a.tables[c], b.tables[c])


# ------------------------------------------------------------------- budgets
def test_over_budget_rejection_carries_exact_remaining_rho(tmp_path):
    plans, margs = _tenant_setup(1)
    per_release = pcost_of_plan(plans[0])
    # budget fits exactly 2 releases plus half of one more
    total = 2.5 * per_release
    srv = _server(tmp_path, plans, rho=total / 2.0)
    try:
        for s in range(2):
            srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0],
                                            seed=s))
        fut = srv.submit(ReleaseRequest(tenant="t0", marginals=margs[0]))
        with pytest.raises(BudgetExhausted) as ei:
            fut.result(120)
        err = ei.value
        assert err.tenant == "t0"
        assert err.requested_pcost == pytest.approx(per_release)
        assert err.remaining_pcost == pytest.approx(0.5 * per_release)
        assert err.remaining_rho == pytest.approx(0.25 * per_release)
        # rejection is pre-measure: ledger unchanged, later top-up would work
        assert srv.ledger.spent("t0") == pytest.approx(2 * per_release)
        st = srv.stats_dict()
        assert st["tenants"]["t0"]["rejected_budget"] == 1
        assert st["tenants"]["t0"]["completed"] == 2
    finally:
        srv.stop()
        srv.ledger.close()


def test_budget_is_per_tenant(tmp_path):
    plans, margs = _tenant_setup(2)
    per = pcost_of_plan(plans[0])
    srv = _server(tmp_path, plans, rho=per / 2.0)   # exactly 1 release each
    try:
        srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0]))
        with pytest.raises(BudgetExhausted):
            srv.request_sync(ReleaseRequest(tenant="t0",
                                            marginals=margs[0]))
        # t0 exhausted, t1 unaffected
        r = srv.request_sync(ReleaseRequest(tenant="t1", marginals=margs[1]))
        assert r.pcost_charged == pytest.approx(per)
    finally:
        srv.stop()
        srv.ledger.close()


def test_malformed_marginals_rejected_before_charge(tmp_path):
    """Marginals with missing cliques or wrong cell counts fail in phase 1,
    BEFORE the ledger is charged — and the worker survives to serve the next
    (valid) request."""
    plans, margs = _tenant_setup(1)
    srv = _server(tmp_path, plans)
    try:
        missing = {c: v for c, v in margs[0].items() if len(c) != 2}
        with pytest.raises(ValueError, match="missing clique"):
            srv.request_sync(ReleaseRequest(tenant="t0", marginals=missing))
        bad_shape = dict(margs[0])
        some_pair = next(c for c in plans[0].cliques if len(c) == 2)
        bad_shape[some_pair] = np.zeros(3)
        with pytest.raises(ValueError, match="cells, want"):
            srv.request_sync(ReleaseRequest(tenant="t0",
                                            marginals=bad_shape))
        # neither malformed request burned any budget
        assert srv.ledger.spent("t0") == 0.0
        # worker alive and still serving: a valid request goes through
        r = srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0]))
        assert r.tables is not None
        assert srv.ledger.spent("t0") == pytest.approx(r.pcost_charged)
        st = srv.stats_dict()
        assert st["tenants"]["t0"]["failed"] == 2
        assert st["tenants"]["t0"]["completed"] == 1
    finally:
        srv.stop()
        srv.ledger.close()


def test_worker_survives_fused_path_failure(tmp_path, monkeypatch):
    """An unexpected exception inside the fused measure_multi path must not
    kill the worker: charged requests fall back to the solo path and still
    resolve (bit-identical, since both paths draw the same noise)."""
    import repro.serve.server as server_mod

    def boom(items, use_kernel=False, dtype=None):
        raise RuntimeError("fused path exploded")

    plans, margs = _tenant_setup(2)
    srv = _server(tmp_path, plans, max_batch=8)
    try:
        monkeypatch.setattr(server_mod, "measure_multi", boom)
        srv.pause()
        futs = [srv.submit(ReleaseRequest(tenant=f"t{i}", marginals=margs[i],
                                          seed=90 + i))
                for i in range(2)]
        srv.resume()
        res = [f.result(120) for f in futs]
        assert not any(r.batched for r in res)     # solo fallback
        assert all(r.tables is not None for r in res)
        # worker alive; fused path restored serves the next batch normally
        monkeypatch.undo()
        r = srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0],
                                            seed=90))
        for c in r.tables:
            assert np.array_equal(r.tables[c], res[0].tables[c])
    finally:
        srv.stop()
        srv.ledger.close()


def test_submit_after_stop_raises(tmp_path):
    plans, margs = _tenant_setup(1)
    srv = _server(tmp_path, plans)
    try:
        srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0]))
    finally:
        srv.stop()
    with pytest.raises(RuntimeError, match="not running"):
        srv.submit(ReleaseRequest(tenant="t0", marginals=margs[0]))
    srv.ledger.close()


def test_register_tenant_mid_traffic(tmp_path):
    """Registering tenants while the worker serves traffic must not corrupt
    the shared engine pool or the session map (lock-guarded)."""
    plans, margs = _tenant_setup(4)
    srv = _server(tmp_path, plans[:1])
    errors = []

    def hammer():
        try:
            for s in range(10):
                srv.request_sync(ReleaseRequest(tenant="t0",
                                                marginals=margs[0], seed=s))
        except Exception as exc:       # noqa: BLE001 — surfaced below
            errors.append(exc)

    t = None
    try:
        import threading
        t = threading.Thread(target=hammer)
        t.start()
        for i in range(1, 4):
            srv.register_tenant(f"t{i}", plans[i], rho=100.0)
            srv.request_sync(ReleaseRequest(tenant=f"t{i}",
                                            marginals=margs[i]))
        t.join(120)
        assert not t.is_alive() and not errors
        assert set(srv.tenants()) == {"t0", "t1", "t2", "t3"}
        assert srv.stats_dict()["tenants"]["t0"]["completed"] == 10
    finally:
        if t is not None and t.is_alive():
            t.join(1)
        srv.stop()
        srv.ledger.close()


def test_unknown_tenant_and_bad_requests(tmp_path):
    plans, margs = _tenant_setup(1)
    srv = _server(tmp_path, plans)
    try:
        with pytest.raises(KeyError):
            srv.request_sync(ReleaseRequest(tenant="ghost",
                                            marginals=margs[0]))
        with pytest.raises(ValueError, match="needs marginals"):
            srv.request_sync(ReleaseRequest(tenant="t0"))
        with pytest.raises(ValueError, match="unknown request kind"):
            srv.request_sync(ReleaseRequest(tenant="t0", kind="nope",
                                            marginals=margs[0]))
        with pytest.raises(ValueError, match="RP\\+ plan"):
            srv.request_sync(ReleaseRequest(tenant="t0", kind="range",
                                            marginals=margs[0]))
        # failures consumed no budget
        assert srv.ledger.spent("t0") == 0.0
    finally:
        srv.stop()
        srv.ledger.close()


# ------------------------------------------------- postprocess + synthesis
def test_nonneg_release_then_synthesis_charges_nothing(tmp_path):
    plans, margs = _tenant_setup(1)
    srv = _server(tmp_path, plans)
    try:
        with pytest.raises(ValueError, match="non-negative release"):
            srv.request_sync(ReleaseRequest(tenant="t0", kind="synthesis",
                                            n_records=50))
        r = srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0],
                                            postprocess="nonneg"))
        assert all(tab.min() >= 0 for tab in r.tables.values())
        spent = srv.ledger.spent("t0")
        s = srv.request_sync(ReleaseRequest(tenant="t0", kind="synthesis",
                                            n_records=200, seed=3))
        assert s.records.shape == (200, DOM.n_attrs)
        assert s.pcost_charged == 0.0
        assert srv.ledger.spent("t0") == spent   # synthesis is postprocessing
    finally:
        srv.stop()
        srv.ledger.close()


# ------------------------------------------------------------- stats + http
def test_stats_dict_and_http_endpoint(tmp_path):
    plans, margs = _tenant_setup(2)
    srv = _server(tmp_path, plans, max_batch=8)
    httpd = None
    try:
        srv.pause()
        futs = [srv.submit(ReleaseRequest(tenant=f"t{i}", marginals=margs[i]))
                for i in range(2)]
        srv.resume()
        [f.result(120) for f in futs]
        st = srv.stats_dict()
        assert st["requests_total"] == 2
        assert st["batch_occupancy"] == pytest.approx(2.0)
        assert st["tenants"]["t0"]["p50_ms"] is not None
        assert st["engine_cache"]["hit_rate"] is not None
        assert st["ledger"]["t0"]["charges"] == 1

        httpd, port = start_stats_http(srv)
        base = f"http://127.0.0.1:{port}"
        health = json.load(urllib.request.urlopen(f"{base}/healthz"))
        assert health["ok"] and set(health["tenants"]) == {"t0", "t1"}
        remote = json.load(urllib.request.urlopen(f"{base}/stats"))
        assert remote["requests_total"] == 2
        ledger = json.load(urllib.request.urlopen(f"{base}/ledger"))
        assert ledger["t1"]["charges"] == 1
    finally:
        if httpd is not None:
            httpd.shutdown()
        srv.stop()
        srv.ledger.close()


def test_healthz_reports_dead_worker_with_503(tmp_path):
    """/healthz is a liveness probe: 200 + ok while the worker thread runs,
    503 + ok=False once it is gone — the same condition submit() refuses on."""
    plans, margs = _tenant_setup(1)
    srv = _server(tmp_path, plans)
    httpd = None
    try:
        srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0]))
        httpd, port = start_stats_http(srv)
        base = f"http://127.0.0.1:{port}"
        health = json.load(urllib.request.urlopen(f"{base}/healthz"))
        assert health["ok"] and health["worker_alive"]
        assert health["queue_depth"] == 0
        assert health["uptime_s"] >= 0
        srv.stop()                          # worker dead, HTTP still up
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz")
        assert ei.value.code == 503
        body = json.load(ei.value)
        assert body["ok"] is False and body["worker_alive"] is False
    finally:
        if httpd is not None:
            httpd.shutdown()
        srv.stop()
        srv.ledger.close()


def test_trace_id_propagates_serve_to_kernel(tmp_path):
    """One traced request yields ONE connected span tree: the trace ID minted
    at submit() reaches the kernel.chain spans inside the fused launch, and
    every span's parent is another span of the same trace."""
    from repro.obs import TRACER
    plans, margs = _tenant_setup(2)
    TRACER.enable()                         # in-memory ring, no file sink
    TRACER.drain()
    try:
        srv = _server(tmp_path, plans, max_batch=8, use_kernel=True)
        try:
            srv.pause()
            futs = [srv.submit(ReleaseRequest(tenant=f"t{i}",
                                              marginals=margs[i], seed=i))
                    for i in range(2)]
            srv.resume()
            res = [f.result(300) for f in futs]
            assert all(r.batched for r in res)
        finally:
            srv.stop()
            srv.ledger.close()
        spans = TRACER.drain()
    finally:
        TRACER.disable()

    by_trace = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    roots = [s for s in spans if s.name == "serve.request"]
    assert len(roots) == 2                  # one root per request
    assert len({r.trace_id for r in roots}) == 2
    for root in roots:
        tree = by_trace[root.trace_id]
        ids = {s.span_id for s in tree}
        orphans = [s for s in tree
                   if s.parent_id is not None and s.parent_id not in ids]
        assert not orphans                  # fully connected tree
        names = {s.name for s in tree}
        assert {"serve.request", "serve.queue_wait", "serve.charge",
                "serve.fuse"} <= names
        assert root.attrs["outcome"] == "completed"
    # the fused launch's kernel spans ride the batch leader's trace
    kernel_spans = [s for s in spans if s.name == "kernel.chain"]
    assert kernel_spans
    assert all(s.trace_id in by_trace for s in kernel_spans)
    leader = [s for s in spans if s.name == "serve.fuse"
              and not s.attrs.get("shared")]
    assert leader and any(s.trace_id == leader[0].trace_id
                          and s.attrs.get("fused") is not None
                          for s in kernel_spans)


@pytest.mark.parametrize("fused", [True, False])
def test_served_release_span_tree(tmp_path, fused):
    """A fused batch and a solo request each yield the validation,
    measurement, transfer and reconstruction spans under the right parents:
    kernel.chain stays a direct child of measure.multi.launch, and
    measure.multi.group keeps the launch's parent."""
    from repro.obs import TRACER
    n = 2 if fused else 1
    plans, margs = _tenant_setup(n)
    TRACER.enable()
    TRACER.drain()
    try:
        srv = _server(tmp_path, plans, max_batch=8, use_kernel=True)
        try:
            srv.pause()
            futs = [srv.submit(ReleaseRequest(tenant=f"t{i}",
                                              marginals=margs[i], seed=i))
                    for i in range(n)]
            srv.resume()
            assert [f.result(300).batched for f in futs] == [fused] * n
        finally:
            srv.stop()
            srv.ledger.close()
        spans = TRACER.drain()
    finally:
        TRACER.disable()
    by_id = {s.span_id: s for s in spans}

    def parent(s):
        return by_id[s.parent_id].name

    def named(name):
        return [s for s in spans if s.name == name]

    (launch,) = named("measure.multi.launch")
    assert parent(launch) == ("serve.fuse" if fused else "engine.measure")
    for name in ("measure.multi.keys", "measure.multi.unpack"):
        (sp,) = named(name)
        assert sp.parent_id == launch.parent_id
    groups = named("measure.multi.group")
    assert len(groups) == 3                     # (), (5,), (5, 5)
    assert all(g.parent_id == launch.parent_id for g in groups)
    chains = named("kernel.chain")
    assert len(chains) == 2 and all(k.parent_id == launch.span_id
                                    for k in chains)
    for name in ("measure.multi.dispatch", "measure.multi.fetch"):
        (sp,) = named(name)
        assert sp.parent_id == launch.span_id and sp.attrs["stage"] == "flat"
        assert sp.attrs["bytes"] > 0
    validates = named("serve.validate")
    assert len(validates) == n
    assert all(parent(v) == "serve.request" for v in validates)
    recs = named("engine.reconstruct")
    assert len(recs) == n
    for rec in recs:
        kids = [s for s in spans if s.parent_id == rec.span_id]
        assert sorted(k.name for k in kids) == [
            "reconstruct.dispatch", "reconstruct.embed", "reconstruct.embed",
            "reconstruct.fetch", "reconstruct.prepare",
            "reconstruct.prepare"]
        assert all(k.attrs["bytes"] > 0 for k in kids
                   if k.name in ("reconstruct.dispatch", "reconstruct.fetch"))
        # one block copy per subset position, over the whole group
        embeds = {k.attrs["dims"]: k for k in kids
                  if k.name == "reconstruct.embed"}
        assert {d: (e.attrs["rows"], e.attrs["copies"])
                for d, e in embeds.items()} == {"5": (3, 2), "5x5": (3, 4)}


def test_worker_idle_span_between_batches(tmp_path):
    """Traced, the worker's wait from the end of one batch to the next drain
    is one serve.idle span; untraced, it records nothing."""
    from repro.obs import TRACER
    plans, margs = _tenant_setup(1)
    srv = _server(tmp_path, plans, max_batch=1)
    try:
        TRACER.enable()
        TRACER.drain()
        try:
            for seed in range(3):
                srv.request_sync(ReleaseRequest(tenant="t0",
                                                marginals=margs[0], seed=seed))
            spans = TRACER.drain()
        finally:
            TRACER.disable()
        srv.request_sync(ReleaseRequest(tenant="t0", marginals=margs[0],
                                        seed=3))
    finally:
        srv.stop()
        srv.ledger.close()
    idles = sorted((s for s in spans if s.name == "serve.idle"),
                   key=lambda s: s.t0)
    drains = sorted(s.t1 for s in spans if s.name == "serve.queue_wait")
    assert len(idles) == 2 and len(drains) == 3
    for idle, drain in zip(idles, drains[1:]):
        assert idle.parent_id is None and idle.t0 < idle.t1 <= drain
    assert not TRACER.drain()


def _expected_bytes(items, nd_dims):
    """Bytes each transfer span should count, from the group shapes alone
    (float32 lanes and σ, uint32[2] keys)."""
    from repro.engine.multi import lane_bucket
    lanes = {}
    for plan, _m, _k in items:
        for c in plan.cliques:
            dims = tuple(plan.domain.attributes[a].size for a in c)
            lanes[dims] = lanes.get(dims, 0) + 1
    want = {"dispatch.flat": 0, "fetch.flat": 0, "dispatch.nd": 0,
            "fetch.nd": 0}
    for dims, g in lanes.items():
        g_pad, m = lane_bucket(g), int(np.prod(dims))
        out = int(np.prod([n - 1 for n in dims]))
        want["dispatch.flat"] += 8 * g_pad              # keys
        if dims in nd_dims:
            want["fetch.flat"] += 4 * g_pad * m         # noise rows out ...
            want["dispatch.nd"] += 4 * (2 * g_pad * m + g)  # ... and back
            want["fetch.nd"] += 4 * g * out
        else:
            want["dispatch.flat"] += 4 * (g_pad * m + g)
            want["fetch.flat"] += 4 * g * out
    return want


@pytest.mark.parametrize("n_items", [1, 3])
def test_transfer_byte_counters_exact(monkeypatch, n_items):
    """Every ``bytes`` attribute equals the sum over its groups' shapes,
    for a solo and a fused batch, with one signature on the per-axis chain
    (so its noise rows make the round trip) and reconstruction's input and
    tables."""
    from repro.core.reconstruct import reconstruct_all_batched
    from repro.obs import TRACER
    nd_dims = {(5, 5)}
    prepare = multi_mod.prepare_chain

    def per_axis_for_nd(factors, dims, batch, **kw):
        if tuple(dims) in nd_dims:
            kw["vmem_budget"] = 16              # over budget: per-axis chain
        return prepare(factors, dims, batch, **kw)

    monkeypatch.setattr(multi_mod, "prepare_chain", per_axis_for_nd)
    plans, margs = _tenant_setup(n_items)
    items = [(p, m, jax.random.PRNGKey(i))
             for i, (p, m) in enumerate(zip(plans, margs))]
    TRACER.enable()
    TRACER.drain()
    try:
        measured = measure_multi(items, use_kernel=True)
        reconstruct_all_batched(plans[0], measured[0], use_kernel=True)
        spans = TRACER.drain()
    finally:
        TRACER.disable()
    got = {}
    for s in spans:
        if s.name.startswith("measure.multi.") and "stage" in s.attrs:
            got[f"{s.name.rsplit('.', 1)[1]}.{s.attrs['stage']}"] = \
                s.attrs["bytes"]
    assert got == _expected_bytes(items, nd_dims)
    cells = sum(int(np.prod([DOM.attributes[a].size for a in c]))
                for c in plans[0].workload.cliques if c)
    (disp,) = [s for s in spans if s.name == "reconstruct.dispatch"]
    (fetch,) = [s for s in spans if s.name == "reconstruct.fetch"]
    assert disp.attrs["bytes"] == fetch.attrs["bytes"] == 4 * cells


def test_metrics_endpoint_parseable_under_concurrent_traffic(tmp_path):
    """16 threads of mixed traffic + /metrics scrapes: every scrape parses,
    and the final exposition agrees with /stats (one backing store)."""
    from repro.obs import parse_exposition
    plans, margs = _tenant_setup(4)
    srv = _server(tmp_path, plans, max_batch=8)
    httpd = None
    errors = []
    try:
        httpd, port = start_stats_http(srv)
        base = f"http://127.0.0.1:{port}"

        def submit(i):
            try:
                for s in range(3):
                    srv.request_sync(ReleaseRequest(
                        tenant=f"t{i % 4}", marginals=margs[i % 4],
                        seed=100 * i + s))
            except Exception as exc:       # noqa: BLE001 — surfaced below
                errors.append(exc)

        def scrape():
            try:
                for _ in range(10):
                    with urllib.request.urlopen(f"{base}/metrics") as resp:
                        assert resp.headers["Content-Type"].startswith(
                            "text/plain; version=0.0.4")
                        parse_exposition(resp.read().decode())
            except Exception as exc:       # noqa: BLE001 — surfaced below
                errors.append(exc)

        import threading
        threads = ([threading.Thread(target=submit, args=(i,))
                    for i in range(8)]
                   + [threading.Thread(target=scrape) for _ in range(8)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors[:3]

        # /metrics and /stats read the same store -> identical numbers
        with urllib.request.urlopen(f"{base}/metrics") as resp:
            parsed = parse_exposition(resp.read().decode())
        st = srv.stats_dict()
        req = parsed["repro_serve_requests_total"]
        for tname, tstats in st["tenants"].items():
            assert req.get(f'tenant="{tname}",outcome="completed"',
                           0) == tstats["completed"]
        assert parsed["repro_serve_batches_total"][""] == st["batches"]
        for tname, led in st["ledger"].items():
            assert parsed["repro_ledger_charges_total"][
                f'tenant="{tname}"'] == led["charges"]
            assert parsed["repro_ledger_pcost_spent"][
                f'tenant="{tname}"'] == pytest.approx(led["pcost_spent"])
    finally:
        if httpd is not None:
            httpd.shutdown()
        srv.stop()
        srv.ledger.close()


# ---------------------------------------------------------------- warm pool
def test_engine_pool_caches_and_counts(tmp_path):
    plans, _ = _tenant_setup(2)
    pool = EnginePool(maxsize=4)
    e0 = pool.engine_for("a", plans[0])
    assert pool.engine_for("a", plans[0]) is e0       # hit
    assert pool.engine_for("b", plans[0]) is e0       # cross-tenant hit
    assert pool.engine_for("b", plans[1]) is not e0
    s = pool.stats()
    assert s["hits"] == 2 and s["misses"] == 2 and s["entries"] == 2


def test_engine_pool_pins_hot_and_evicts_cold():
    wk_a = all_kway(Domain.create([4, 3]), 2, include_lower=True)
    plans = [select(all_kway(DOM, 2, include_lower=True), pcost_budget=1.0)
             for _ in range(3)] + [select(wk_a, pcost_budget=1.0)]
    pool = EnginePool(maxsize=2, pin_count=1)
    hot = pool.engine_for("a", plans[0])
    for _ in range(5):                       # "a" hammers plan 0 -> hot, pinned
        pool.engine_for("a", plans[0])
    assert len(pool.cache._pinned) == 1
    pool.engine_for("b", plans[1])           # fills the cache
    pool.engine_for("c", plans[2])           # evicts ... someone unpinned
    pool.engine_for("d", plans[3])
    assert pool.cache.evictions == 2
    assert pool.engine_for("a", plans[0]) is hot      # hot engine survived
    assert pool.stats()["snapshot"]          # snapshot renders


def test_engine_cache_weighted_eviction_prefers_low_score():
    from repro.engine.sharded import _EngineCache
    import jax.numpy as jnp

    class _P:                                # minimal plan stand-in
        def engine(self, **kw):
            raise AssertionError("not used")

    cache = _EngineCache(maxsize=2)
    p1, p2, p3 = _P(), _P(), _P()
    cache.put(p1, False, jnp.float32, "e1")
    cache.put(p2, False, jnp.float32, "e2")
    scores = {cache._key(p1, False, jnp.float32): 5.0,
              cache._key(p2, False, jnp.float32): 1.0}
    cache.evict_score = lambda k: scores.get(k, 0.0)
    cache.put(p3, False, jnp.float32, "e3")  # evicts p2 (lowest score)
    assert cache.get(p1, False, jnp.float32) == "e1"
    assert cache.get(p2, False, jnp.float32) is None
    assert cache.evictions == 1


def test_engine_cache_pinned_entry_survives_lru():
    from repro.engine.sharded import _EngineCache
    import jax.numpy as jnp

    class _P:
        def engine(self, **kw):
            raise AssertionError("not used")

    cache = _EngineCache(maxsize=2)
    keep, other, third = _P(), _P(), _P()
    cache.put(keep, False, jnp.float32, "keep")
    cache.pin(keep, False, jnp.float32)
    cache.put(other, False, jnp.float32, "other")
    cache.put(third, False, jnp.float32, "third")   # LRU would evict "keep"
    assert cache.get(keep, False, jnp.float32) == "keep"
    assert cache.get(other, False, jnp.float32) is None
    # all-pinned cache still makes room (advisory pins)
    cache.pin(third, False, jnp.float32)
    fourth = _P()
    cache.put(fourth, False, jnp.float32, "fourth")
    assert cache.forced_evictions == 1
