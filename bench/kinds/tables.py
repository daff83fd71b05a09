"""The ``tables`` deployment: each tenant's client computes the marginal
tables of its records and submits them with every request.

The plan is every marginal of up to ``kmax`` attributes, selected for the
configuration's cost of one release.  A tenant's tables come from
``traffic.tenant_streams``: seeded records, grown by a fresh batch before
each release, each request carrying its own copy.  The check recomputes a
sampled release from those tables and the request's seed with the float64
reference, and reads the ledger's journal back.
"""
from __future__ import annotations

import math
from typing import Dict, List, Mapping, Tuple

import numpy as np

from bench import traffic
from bench.check import Reference, ledger_numbers


class Deployment:
    """Client-computed tables, one stream of releases per tenant."""

    def __init__(self, cell, seed: int, devices) -> None:
        from repro.core import Domain, all_kway, select
        from repro.serve import ReleaseRequest

        cfg, mix = cell.config, cell.mix
        self._request = ReleaseRequest
        self.cfg = cfg
        self.sizes = list(cfg["sizes"])
        dom = Domain.create(self.sizes)
        wk = all_kway(dom, int(cfg["kmax"]), include_lower=True).reweighted(
            cfg["weights"])
        self.plan = select(wk, pcost_budget=float(cfg["pcost_per_release"]))
        self.workload = traffic.workload_cliques(len(self.sizes),
                                                 int(cfg["kmax"]))
        self.layout = traffic.TableLayout(self.sizes,
                                          traffic.closure_order(self.workload))
        n_tenants = int(mix["tenants"])
        self.streams = traffic.tenant_streams(
            self.layout, seed, n_tenants, int(cfg["records_per_tenant"]),
            int(cfg["records_per_release"]), float(cfg["zipf_skew"]))
        self.tenants = [f"tenant-{t}" for t in range(n_tenants)]
        self.server_kwargs: Dict[str, object] = {}

    def register(self, srv) -> None:
        for name in self.tenants:
            srv.register_tenant(name, self.plan,
                                pcost=float(self.cfg["pcost_total"]))

    def next_request(self, t: int):
        stream = self.streams[t]
        r, buf = stream.next_request()
        seed = stream.noise_seed(r)
        req = self._request(tenant=self.tenants[t],
                            marginals=self.layout.views(buf), seed=seed)
        return r, req, (seed, buf)

    def layer_inputs(self, srv) -> dict:
        from bench import layers
        return {"plan_groups": layers.reconstruct_groups(self.sizes,
                                                         self.workload),
                "chain_fused": layers.chain_paths(srv, self.plan)}

    def drop(self) -> None:
        del self.streams, self.plan

    def check(self, sample: List[Tuple[tuple, object]], journal: str,
              submitted: Mapping[str, int]) -> Dict[str, float]:
        ref = Reference(self.sizes, self.workload, self.layout.cliques,
                        float(self.cfg["pcost_per_release"]))
        numbers = {"meas_ulp": 0.0, "table_sd": 0.0, "sigma2_rel": 0.0}
        for (nseed, buf), res in sample:
            margs = self.layout.views(buf.astype(np.float64))
            got = ref.compare(
                ref.release(margs, nseed),
                {c: m.omega for c, m in res.measurements.items()},
                {c: m.sigma2 for c, m in res.measurements.items()},
                res.tables)
            for k, v in got.items():
                numbers[k] = max(numbers[k], v)
        if not sample:
            numbers = {k: math.inf for k in numbers}
        numbers.update(ledger_numbers(journal, submitted, ref.pcost))
        return numbers
