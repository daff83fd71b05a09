#!/usr/bin/env python3
"""Smoke run of the multi-tenant release path on one TPU chip.

Drives ``ReleaseServer`` through the entry points a deployment calls —
``BudgetLedger``, ``register_tenant``, ``submit`` — at the full Adult width:

* four marginal tenants on the Adult ≤3-way workload (14 attributes, 470
  cliques, 21,043,262 marginal cells per release), each with its own
  1,000,000 synthetic records; two releases per tenant are queued while the
  worker is paused and then fused into one cross-tenant launch;
* one ResidualPlanner+ tenant on the Synth-10^20 range workload;
* one secure (discrete-Gaussian) tenant on Adult ≤2-way (148,726 cells).

Every phase is checked, and any failure exits non-zero:

* each released table lies within a 6σ band of the exact marginals (σ from
  the plan's per-cell variance; the band widens to the Bonferroni bound at
  family-wise error 1e-6 once a release has more cells than 6σ covers);
* the fused multi-tenant measurements equal, bit for bit, ``measure(plan,
  marginals, PRNGKey(seed))`` run on the same device;
* the secure release carries ``measure_discrete``'s σ̄/γ² and, with the
  noise zeroed, its transforms agree with the float64 oracle;
* the Pallas chains ran (``pallas_calls > 0`` and ``fused_chains > 0``), no
  fused launch fell back to the solo path, and reopening the ledger replays
  every charge.

Earlier lines print planning, compile and wall seconds per phase, compiled
chain counts and peak device bytes (a record, not a benchmark).  The last
line is one JSON object: ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the sharded measurement (records sharded over a
4-device ``("data",)`` mesh) against the same call on one device.

Run from the checkout root (no install needed)::

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the four-chip phase only

There is no CPU fallback: without a TPU the script exits non-zero and prints
no result line.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Sequence

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


@dataclass(frozen=True)
class SmokeConfig:
    """Sizes of the smoke.  The defaults are the run; smaller configs exist
    only so the phases can be rehearsed off the chip."""

    sizes: Optional[Sequence[int]] = None   # None: the Adult schema
    kmax: int = 3
    tenants: int = 4
    releases: int = 2
    n_records: int = 1_000_000
    secure_kmax: int = 2
    rplus_n: int = 10
    rplus_d: int = 20
    sharded_records: int = 1_000_000
    use_kernel: Optional[bool] = None       # None: the backend's own path


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"ok: {what}")


class CompileClock:
    """Sums XLA backend-compile time and counts compiles (jax.monitoring)."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def marginal_workload(cfg: SmokeConfig, kmax: int):
    from repro.configs import adult_marginals
    from repro.core import Domain, all_kway
    if cfg.sizes is None:
        return adult_marginals.make(kmax=kmax)
    dom = Domain.create(list(cfg.sizes))
    return dom, all_kway(dom, kmax, include_lower=True).reweighted("cells")


def z_bound(n_cells: int) -> float:
    """6σ, or the two-sided Bonferroni z at family-wise error 1e-6 when a
    release has so many cells that 6σ excursions become expected."""
    from statistics import NormalDist
    alpha = 1e-6 / max(n_cells, 1)
    return max(6.0, NormalDist().inv_cdf(1.0 - alpha / 2.0))


def max_z(tables, exact, sd) -> float:
    import numpy as np
    worst = 0.0
    for c, t in tables.items():
        dev = np.abs(np.asarray(t, np.float64).reshape(-1)
                     - np.asarray(exact[c], np.float64).reshape(-1))
        worst = max(worst, float(dev.max(initial=0.0)) / sd(c))
    return worst


def run_single(cfg: SmokeConfig, workdir: Path) -> dict:
    """The one-chip phases; returns the record printed before the result.
    The tenants' budget ledger is written under ``workdir``."""
    import numpy as np

    import jax

    from repro.core import measure, select
    from repro.core.discrete import (DiscreteMeasurement, clique_gamma2,
                                     discrete_pcost_of_plan, measure_discrete)
    from repro.core.domain import all_kway
    from repro.core.kron import kron_matvec_np
    from repro.core.plus import (PlusSchema, cell_variances_plus,
                                 reconstruct_plus, select_plus)
    from repro.data.tabular import (marginals_from_records, synth_domain,
                                    synthetic_records)
    from repro.kernels.kron_matvec.fused import launched_chains
    from repro.kernels.kron_matvec.stats import chain_stats, reset_chain_stats
    from repro.serve import BudgetLedger, ReleaseRequest, ReleaseServer

    rec: dict = {}
    clock = CompileClock()
    reset_chain_stats()

    # ---- plans ----------------------------------------------------------
    t0 = time.monotonic()
    dom, wk = marginal_workload(cfg, cfg.kmax)
    plan = select(wk, pcost_budget=1.0)
    sdom, swk = marginal_workload(cfg, cfg.secure_kmax)
    splan = select(swk, pcost_budget=1.0)
    rdom = synth_domain(cfg.rplus_n, cfg.rplus_d, kind="numeric")
    rwk = all_kway(rdom, min(cfg.kmax, cfg.rplus_d), include_lower=True)
    schema = PlusSchema.create(rdom, ["range"] * cfg.rplus_d)
    rplan = select_plus(rwk, schema, 1.0)
    rec["planning_s"] = time.monotonic() - t0
    cells = sum(dom.n_cells(c) for c in wk.cliques)
    log(f"plans: {len(plan.cliques)} cliques / {cells} cells per marginal "
        f"release; secure {sum(sdom.n_cells(c) for c in swk.cliques)} cells;"
        f" RP+ {len(rplan.cliques)} cliques "
        f"({rec['planning_s']:.1f} s)")

    # ---- records -> exact marginals (host, float64) ----------------------
    t0 = time.monotonic()
    margs = []
    for t in range(cfg.tenants):
        records = synthetic_records(dom, cfg.n_records, seed=t)
        margs.append(marginals_from_records(dom, plan.cliques, records))
    srecords = synthetic_records(sdom, cfg.n_records, seed=100)
    smargs = marginals_from_records(sdom, splan.cliques, srecords)
    rrecords = synthetic_records(rdom, cfg.n_records, seed=200)
    rmargs = marginals_from_records(rdom, rplan.cliques, rrecords)
    rec["data_s"] = time.monotonic() - t0
    log(f"records and exact marginals: {rec['data_s']:.1f} s")

    # ---- server ----------------------------------------------------------
    ledger_path = workdir / "chip_smoke_ledger.jsonl"
    workdir.mkdir(parents=True, exist_ok=True)
    if ledger_path.exists():
        ledger_path.unlink()
    ledger = BudgetLedger(str(ledger_path))
    srv = ReleaseServer(ledger, max_batch=64, use_kernel=cfg.use_kernel)
    check(srv.use_kernel, "the server routes chains through the Pallas "
                          "kernels")
    srv.start()
    try:
        t0 = time.monotonic()
        per_release = None
        for t in range(cfg.tenants):
            srv.register_tenant(f"adult-{t}", plan,
                                pcost=1.25 * cfg.releases)
        srv.register_tenant("rplus", rplan, pcost=1.25)
        srv.register_tenant("secure", splan, pcost=1.25, secure=True)
        rec["register_s"] = time.monotonic() - t0
        log(f"registered {cfg.tenants + 2} tenants: "
            f"{rec['register_s']:.1f} s")

        # ---- fused multi-tenant marginal releases --------------------
        c0, n0 = clock.seconds, clock.count
        t0 = time.monotonic()
        srv.pause()
        futs = []
        for t in range(cfg.tenants):
            for r in range(cfg.releases):
                seed = 1000 * t + r
                futs.append((t, seed, srv.submit(ReleaseRequest(
                    tenant=f"adult-{t}", marginals=margs[t], seed=seed))))
        srv.resume()
        results = [(t, seed, f.result(timeout=1200)) for t, seed, f in futs]
        rec["fused_release_s"] = time.monotonic() - t0
        rec["fused_release_compile_s"] = clock.seconds - c0
        log(f"fused batch of {len(results)} releases: "
            f"{rec['fused_release_s']:.1f} s wall, "
            f"{rec['fused_release_compile_s']:.1f} s compiling "
            f"({clock.count - n0} programs)")
        check(all(r.batched for _t, _s, r in results),
              "every marginal release was served in the fused batch")
        per_release = results[0][2].pcost_charged

        # ---- oracle band ---------------------------------------------
        t0 = time.monotonic()
        bound = z_bound(cells * len(results))
        worst = max(max_z(r.tables, margs[t],
                          lambda c: math.sqrt(plan.marginal_variance(c)))
                    for t, _s, r in results)
        rec["oracle_check_s"] = time.monotonic() - t0
        log(f"max |z| over {cells * len(results)} cells: {worst:.3f} "
            f"(bound {bound:.3f})")
        check(worst <= bound, "fused releases agree with the exact "
                              "marginals within the 6σ band")

        # ---- fused == sequential, bit for bit ------------------------
        c0 = clock.seconds
        t0 = time.monotonic()
        for t, seed, r in results:
            seq = measure(plan, margs[t], jax.random.PRNGKey(seed),
                          use_kernel=cfg.use_kernel)
            same = all(np.array_equal(seq[c].omega, r.measurements[c].omega)
                       for c in plan.cliques)
            check(same, f"tenant {t} seed {seed}: fused measurements equal "
                        f"measure(plan, marginals, PRNGKey(seed))")
        rec["sequential_measure_s"] = time.monotonic() - t0
        rec["sequential_measure_compile_s"] = clock.seconds - c0

        # ---- RP+ range tenant ----------------------------------------
        t0 = time.monotonic()
        subset = [c for c in rplan.workload.cliques if len(c) <= 2]
        subset += [c for c in rplan.workload.cliques if len(c) == 3][:8]
        rr = srv.request_sync(ReleaseRequest(
            tenant="rplus", kind="range", marginals=rmargs, seed=7,
            cliques=subset), timeout=1200)
        rec["rplus_release_s"] = time.monotonic() - t0
        worst_rel = 0.0
        worst_z = 0.0
        for c in subset:
            want = reconstruct_plus(rplan, rr.measurements, c)
            got = np.asarray(rr.tables[c], np.float64)
            worst_rel = max(worst_rel, float(np.abs(got - want).max())
                            / max(float(np.abs(want).max()), 1.0))
            truth = kron_matvec_np([schema.bases[i].W for i in c],
                                   rmargs[c], [schema.bases[i].n for i in c])
            sd = np.sqrt(cell_variances_plus(schema, rplan.sigmas, c))
            worst_z = max(worst_z, float((np.abs(got - truth) / sd).max()))
        log(f"RP+ release of {len(subset)} range marginals: "
            f"{rec['rplus_release_s']:.1f} s; reconstruction vs fp64 "
            f"oracle rel err {worst_rel:.2e}; max |z| {worst_z:.3f}")
        check(worst_rel < 1e-4, "RP+ device reconstruction agrees with the "
                                "float64 oracle")
        check(worst_z <= z_bound(sum(schema.query_rows(c) for c in subset)),
              "RP+ range answers lie within the 6σ band of the exact ones")

        # ---- secure tenant -------------------------------------------
        t0 = time.monotonic()
        sr = srv.request_sync(ReleaseRequest(
            tenant="secure", marginals=smargs, seed=11), timeout=1200)
        rec["secure_release_s"] = time.monotonic() - t0
        ok_params = True
        ratio = 1.0
        for c in splan.cliques:
            m = sr.measurements[c]
            sb, g2, _n = clique_gamma2(splan, c, 4)
            ok_params &= (isinstance(m, DiscreteMeasurement)
                          and m.sigma_bar == sb and m.gamma2 == g2)
            ratio = max(ratio, float(sb) ** 2 / splan.sigmas[c])
        check(ok_params, "secure measurements carry measure_discrete's "
                         "σ̄ and γ²")
        check(abs(sr.pcost_charged - discrete_pcost_of_plan(splan)) < 1e-12,
              "the secure tenant is charged the exact discrete pcost")
        sworst = max_z(sr.tables, smargs, lambda c: math.sqrt(
            ratio * splan.marginal_variance(c)))
        scells = sum(sdom.n_cells(c) for c in swk.cliques)
        check(sworst <= z_bound(scells), f"secure release within the 6σ "
                                         f"band (max |z| {sworst:.3f})")
        seng = srv.pool.engine_for("secure", splan, srv.use_kernel,
                                   srv.dtype, True, 4)

        def zero(_g2, size, _rng):
            return np.zeros(size, np.int64)

        em = seng.measure(smargs, jax.random.PRNGKey(0),
                          _noise_override=zero)
        dm = measure_discrete(splan, smargs, np.random.default_rng(0),
                              _noise_override=zero)
        rel = max(float(np.abs(em[c].omega - dm[c].omega).max())
                  / max(float(np.abs(dm[c].omega).max()), 1.0)
                  for c in splan.cliques)
        log(f"secure release: {rec['secure_release_s']:.1f} s; zero-noise "
            f"transforms vs measure_discrete rel err {rel:.2e}")
        check(rel < 1e-5, "secure transforms agree with measure_discrete")

        # ---- kernel and fallback counters ----------------------------
        st = chain_stats()
        rec["chain_stats"] = st
        rec["compiled_chains"] = launched_chains()
        log(f"chain stats {st}; compiled chains: {rec['compiled_chains']}")
        check(st["pallas_calls"] > 0 and st["fused_chains"] > 0,
              "the Pallas chain kernels ran")
        check(srv.stats.fused_fallbacks == 0,
              "no fused launch fell back to the solo path")
    finally:
        srv.stop()
        ledger.close()

    # ---- ledger replay ---------------------------------------------------
    reopened = BudgetLedger(str(ledger_path))
    try:
        want = {f"adult-{t}": cfg.releases * per_release
                for t in range(cfg.tenants)}
        want["rplus"] = rr.pcost_charged
        want["secure"] = sr.pcost_charged
        check(all(abs(reopened.spent(t) - v) <= 1e-9 * max(1.0, v)
                  for t, v in want.items()),
              "reopening the ledger replays every charge")
    finally:
        reopened.close()

    rec["compile_s"] = clock.seconds
    rec["compiles"] = clock.count
    rec["peak_device_bytes"] = peak_bytes()
    return rec


def run_four(cfg: SmokeConfig) -> dict:
    """Sharded measurement over a 4-device mesh vs the same call on one."""
    import numpy as np

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import select
    from repro.data.tabular import marginals_from_records, synthetic_records
    from repro.engine.sharded import sharded_marginals, sharded_measure

    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {len(devs)}")
    rec: dict = {}
    clock = CompileClock()
    dom, wk = marginal_workload(cfg, cfg.secure_kmax)
    plan = select(wk, pcost_budget=1.0)
    records = synthetic_records(dom, cfg.sharded_records, seed=0)
    exact = marginals_from_records(dom, plan.cliques, records)
    mesh = Mesh(np.array(devs[:4]), ("data",))
    key = jax.random.PRNGKey(3)

    t0 = time.monotonic()
    sharded = jax.device_put(records, NamedSharding(mesh, P("data", None)))
    m4 = sharded_marginals(dom, plan.cliques, sharded, mesh)
    meas4 = sharded_measure(plan, sharded, key, mesh=mesh,
                            use_kernel=cfg.use_kernel)
    rec["sharded_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    single = jax.device_put(records, devs[0])
    m1 = sharded_marginals(dom, plan.cliques, single, None)
    meas1 = sharded_measure(plan, single, key, mesh=None,
                            use_kernel=cfg.use_kernel)
    rec["single_s"] = time.monotonic() - t0
    rec["records"] = cfg.sharded_records
    rec["compile_s"] = clock.seconds
    log(f"{cfg.sharded_records} records over {len(plan.cliques)} cliques: "
        f"4-device {rec['sharded_s']:.1f} s, 1-device {rec['single_s']:.1f} s"
        f" ({clock.seconds:.1f} s compiling)")
    check(all(np.array_equal(np.asarray(m4[c]), np.asarray(m1[c]))
              and np.array_equal(np.asarray(m1[c]), exact[c])
              for c in plan.cliques),
          "4-device marginal tables equal the 1-device and exact tables")
    check(all(np.array_equal(meas4[c].omega, meas1[c].omega)
              for c in plan.cliques),
          "4-device measurements equal the 1-device measurements")
    rec["peak_device_bytes"] = peak_bytes()
    return rec


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args()
    try:
        from repro.runtime import enable_compile_cache
    except ImportError as e:
        log(f"FAIL: the repro package is not next to this script ({e})")
        return 2
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        log(f"FAIL: no TPU: JAX's default device is {dev.platform!r}; "
            f"this smoke has no CPU fallback")
        return 1
    log(f"device {dev.device_kind} x{len(devs)}; compile cache {cache_dir}")
    t0 = time.monotonic()
    try:
        rec = run_four(SmokeConfig()) if args.chips == 4 \
            else run_single(SmokeConfig(), ROOT / ".smoke")
        check("repro.launch.dryrun" not in sys.modules,
              "nothing on the served path imported launch/dryrun.py")
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    rec["total_s"] = time.monotonic() - t0
    log("record " + json.dumps(rec, default=str, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
