"""The ``records`` deployment: the service holds each tenant's records on
the device, each request carries a fresh batch of rows, and the service
counts the release's tables from the records as they stand.

It is the ``tables`` kind with the counting moved into the service: the
plan, the layout, the per-layer inputs and the comparison of a release
with the float64 reference are that kind's.  The base records and each
release's fresh batch are the ones ``tables`` counts on the client
(``traffic.zipf_records`` over ``traffic.rng_for``, and
``TenantStream.fresh_batch``), so a release's tables are, bit for bit, the
tables that kind would have shipped.

A tenant's clients submit concurrently, so the order in which the service
took a tenant's batches is the order of its charges: each request names its
release in the ledger (``request_id`` ``<tenant>/<release>``), and the check
reads from the journal the batches charged up to and including a sampled
release's own.  It rebuilds those tables on the host in float64
(``TableLayout.tables`` of the base records, ``add`` of each batch) and
hands them to the ``tables`` check.
"""
from __future__ import annotations

import dataclasses
import json
import math
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Mapping, Tuple

import numpy as np

from bench import traffic
from bench.kinds import tables


class Deployment(tables.Deployment):
    """Records held by the service, one stream of fresh batches per
    tenant."""

    def __init__(self, cell, seed: int, devices) -> None:
        # The client holds no base records here: its streams start from
        # empty tables and serve only their seeded draws (fresh_batch,
        # noise_seed).  The service is handed the base records instead.
        super().__init__(dataclasses.replace(
            cell, config={**cell.config, "records_per_tenant": 0}),
            seed, devices)
        self.cfg = cell.config
        self.seed = seed
        self._next = [0] * len(self.tenants)      # guarded-by: _lock
        self._lock = threading.Lock()

    def base_records(self, t: int) -> np.ndarray:
        """Tenant ``t``'s records at registration, as ``tables`` draws them."""
        return traffic.zipf_records(
            traffic.rng_for(self.seed, traffic._BASE, t), self.sizes,
            int(self.cfg["records_per_tenant"]), float(self.cfg["zipf_skew"]))

    def register(self, srv) -> None:
        with ThreadPoolExecutor(max_workers=min(8, len(self.tenants))) as ex:
            held = ex.map(self.base_records, range(len(self.tenants)))
            for name, recs in zip(self.tenants, held):
                srv.register_tenant(name, self.plan,
                                    pcost=float(self.cfg["pcost_total"]),
                                    records=recs)

    def next_request(self, t: int):
        with self._lock:
            r = self._next[t]
            self._next[t] += 1
        stream = self.streams[t]
        seed = stream.noise_seed(r)
        req = self._request(tenant=self.tenants[t],
                            records=stream.fresh_batch(r), seed=seed,
                            request_id=f"{self.tenants[t]}/{r}")
        return r, req, (t, r, seed)

    def drop(self) -> None:
        # The check draws the fresh batches again from the streams.
        del self.plan

    def taken(self, journal: str) -> Dict[str, List[int]]:
        """Per tenant, the releases whose batches the service took, in the
        order it charged them."""
        out: Dict[str, List[int]] = {name: [] for name in self.tenants}
        with open(journal, encoding="utf-8") as fh:
            for line in fh:
                if not line.strip():
                    continue
                rec = json.loads(line)
                if rec.get("op") == "charge":
                    name, r = str(rec["request_id"]).rsplit("/", 1)
                    out[name].append(int(r))
        return out

    def tables(self, t: int, releases: List[int], base: np.ndarray
               ) -> np.ndarray:
        """Tenant ``t``'s tables after the batches of ``releases``, float64."""
        buf = base.copy()
        for r in releases:
            self.layout.add(buf, self.streams[t].fresh_batch(r))
        return buf

    def check(self, sample: List[Tuple[tuple, object]], journal: str,
              submitted: Mapping[str, int]) -> Dict[str, float]:
        order = self.taken(journal)
        rebuilt: List[Tuple[tuple, object]] = []
        missing = False
        bases: Dict[int, np.ndarray] = {}
        for (t, r, nseed), res in sample:
            charged = order[self.tenants[t]]
            if r not in charged:
                missing = True
                continue
            if t not in bases:
                bases[t] = self.layout.tables(self.base_records(t)).astype(
                    np.float64)
            buf = self.tables(t, charged[:charged.index(r) + 1], bases[t])
            rebuilt.append(((nseed, buf), res))
        numbers = super().check(rebuilt, journal, submitted)
        if missing:
            # A sampled release whose charge the journal lacks.
            numbers.update({k: math.inf for k in
                            ("meas_ulp", "table_sd", "sigma2_rel")})
        return numbers
