"""Mesh-sharded measurement engine (beyond-paper scale-out of Algorithm 1).

Records are sharded over the ('pod','data') axes; every device builds partial
marginal tables for the plan's closure via a one-hot matmul (MXU-friendly —
no scatters), partial tables are psum'd, and the residual transform + noise
run replicated (noise keys are identical across devices, so each device holds
the same noisy answers — measurement is read-only on the records).

The paper notes base mechanisms "can be run in parallel" (§5.2); this module
is that observation turned into a pjit/shard_map program.  The replicated
transform is served by whatever engine the plan's family provides via the
unified plan protocol (``plan.engine(...)``, docs/DESIGN.md §9) — plain
plans route through :class:`~repro.engine.engine.MarginalEngine`, RP+ plans
through :class:`~repro.engine.plus_engine.PlusEngine`; this module never
branches on the concrete plan type.
"""
from __future__ import annotations

import os
import weakref
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.domain import Clique, Domain
from repro.core.mechanism import Measurement, noise_dtype
from repro.core.plantable import BasePlan
from repro.kernels.kron_matvec._layout import resolve_use_kernel
from repro.obs import REGISTRY

# Process-wide engine-cache event feed for /metrics (per-cache ints stay on
# each _EngineCache instance; this family aggregates across caches).
_CACHE_EVENTS = REGISTRY.counter(
    "repro_engine_cache_events_total",
    "Engine-cache events (hit, miss, eviction, forced_eviction)",
    labels=("event",))


def _env_cache_size(default: int = 16) -> int:
    """REPRO_ENGINE_CACHE_SIZE env override of the engine-cache capacity."""
    raw = os.environ.get("REPRO_ENGINE_CACHE_SIZE", "")
    try:
        return max(1, int(raw))
    except ValueError:
        return default


class _EngineCache:
    """LRU cache of compiled serving engines, weak-safely keyed on the plan.

    Entries are keyed on ``(id(plan), use_kernel, dtype)`` but each holds a
    ``weakref`` to its plan and is validated with an identity check on every
    hit — a recycled ``id`` can never alias a stale engine.  A full cache
    evicts exactly the least-recently-used entry (the historical wholesale
    ``.clear()`` threw away every warm engine on the 17th plan).  Cached
    engines pin their plan (``engine.plan``), so entries normally leave via
    LRU eviction; the per-plan ``weakref.finalize`` additionally drops
    entries whose values don't pin the plan the moment it is collected.

    Capacity is configurable: constructor arg, else the
    ``REPRO_ENGINE_CACHE_SIZE`` environment variable, else 16.  ``hits`` /
    ``misses`` aggregate across entries; each served engine's own
    ``EngineStats`` additionally records its per-engine ``cache_hits`` /
    ``cache_misses`` provenance.

    Warm-pool hooks (docs/DESIGN.md §13): ``pin``/``unpin`` exempt an entry
    from eviction (a full cache of pinned entries still evicts LRU — pins are
    advisory, counted in ``forced_evictions``), and an ``evict_score``
    callback, when set, picks the victim with the LOWEST score among unpinned
    entries (ties broken LRU) instead of pure LRU — the release server's
    :class:`~repro.serve.pool.EnginePool` scores by tenant-weighted use.
    """

    def __init__(self, maxsize: Optional[int] = None):
        self.maxsize = _env_cache_size() if maxsize is None else int(maxsize)
        if self.maxsize < 1:
            raise ValueError(f"cache maxsize must be >= 1, got {maxsize}")
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.forced_evictions = 0
        self.evict_score = None        # Optional[Callable[[tuple], float]]
        self._pinned: set = set()
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._finalized: set = set()

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _child_plans(plan) -> tuple:
        """Child plans of a composite plan (empty for monolithic plans)."""
        return tuple(getattr(plan, "block_plans", None) or ())

    def _key(self, plan, use_kernel: bool, dtype, secure: bool = False,
             digits: int = 4) -> tuple:
        # digits is part of the key: a secure engine's σ̄/γ² are baked in at
        # construction, so two rationalizations must never share an engine
        # (the noise served would disagree with the privacy charged).
        # Composite plans additionally key on their child-plan identities:
        # a composite entry is only valid while the exact block plans it was
        # compiled against are alive, and _drop_plan distinguishes "this id
        # is the entry's own plan" (drop it) from "this id is one of its
        # children" (drop the parent, never the siblings).
        return ((id(plan), tuple(map(id, self._child_plans(plan)))),
                bool(use_kernel), jnp.dtype(dtype).name,
                bool(secure), int(digits) if secure else None)

    def get(self, plan, use_kernel: bool, dtype, secure: bool = False,
            digits: int = 4):
        key = self._key(plan, use_kernel, dtype, secure, digits)
        ent = self._entries.get(key)
        if ent is None:
            self.misses += 1
            _CACHE_EVENTS.labels(event="miss").inc()
            return None
        ref, child_refs, engine = ent
        stale = ref() is not plan      # id recycled: stale entry
        if not stale:
            children = self._child_plans(plan)
            stale = len(child_refs) != len(children) or any(
                r() is not c for r, c in zip(child_refs, children))
        if stale:
            del self._entries[key]
            self._pinned.discard(key)
            self.misses += 1
            _CACHE_EVENTS.labels(event="miss").inc()
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        _CACHE_EVENTS.labels(event="hit").inc()
        stats = getattr(engine, "stats", None)
        if stats is not None:          # cache values are engines in serving;
            stats.bump("cache_hits")   # tests may stash sentinels
        return engine

    def put(self, plan, use_kernel: bool, dtype, engine,
            secure: bool = False, digits: int = 4) -> None:
        key = self._key(plan, use_kernel, dtype, secure, digits)
        while len(self._entries) >= self.maxsize:
            self._evict_one()
        self._entries[key] = (weakref.ref(plan),
                              tuple(weakref.ref(c)
                                    for c in self._child_plans(plan)),
                              engine)
        if id(plan) not in self._finalized:
            self._finalized.add(id(plan))
            weakref.finalize(plan, self._drop_plan, id(plan))

    def _evict_one(self) -> None:
        """Evict one entry: lowest evict_score among unpinned (ties → LRU),
        else LRU among unpinned, else LRU outright (advisory pins)."""
        candidates = [k for k in self._entries if k not in self._pinned]
        if not candidates:                          # everything pinned
            self.forced_evictions += 1
            _CACHE_EVENTS.labels(event="forced_eviction").inc()
            victim = next(iter(self._entries))      # oldest = LRU
        elif self.evict_score is not None:
            victim = min(candidates, key=lambda k: (
                self.evict_score(k), list(self._entries).index(k)))
        else:
            victim = candidates[0]                  # LRU among unpinned
        del self._entries[victim]
        self._pinned.discard(victim)
        self.evictions += 1
        _CACHE_EVENTS.labels(event="eviction").inc()

    # ---------------------------------------------------------- warm pool
    def pin(self, plan, use_kernel: bool, dtype, secure: bool = False,
            digits: int = 4) -> None:
        self._pinned.add(self._key(plan, use_kernel, dtype, secure, digits))

    def unpin(self, plan, use_kernel: bool, dtype, secure: bool = False,
              digits: int = 4) -> None:
        self._pinned.discard(self._key(plan, use_kernel, dtype, secure,
                                       digits))

    def snapshot(self) -> list:
        """One dict per live entry (for /stats): key fields + pin state."""
        rows = []
        for key in self._entries:
            (pid, child_ids), use_kernel, dtype, secure, digits = key
            rows.append(dict(plan_id=pid, n_children=len(child_ids),
                             use_kernel=use_kernel, dtype=dtype,
                             secure=secure, pinned=key in self._pinned))
        return rows

    def _drop_plan(self, pid: int) -> None:
        # Drop entries OWNED by this plan id, and composite entries that held
        # it as a child (their engine references a dead block plan).  A dying
        # composite parent matches only its own entries — the children's
        # entries key on (child_id, ()) and survive, still serving any other
        # owner of those block plans (they were never orphaned *stale*; they
        # are independently validated on every hit).
        self._finalized.discard(pid)
        for k in [k for k in self._entries
                  if k[0][0] == pid or pid in k[0][1]]:
            del self._entries[k]
            self._pinned.discard(k)


# Engines cached per (plan, path, dtype, secure): repeated sharded_measure
# calls on one plan reuse the jitted group transforms instead of re-tracing.
# Capacity from REPRO_ENGINE_CACHE_SIZE (default 16).
_ENGINE_CACHE = _EngineCache()


def _engine_for(plan: BasePlan, use_kernel: bool, dtype,
                secure: bool = False, digits: int = 4):
    eng = _ENGINE_CACHE.get(plan, use_kernel, dtype, secure, digits)
    if eng is None:
        eng = plan.engine(use_kernel=use_kernel, precompile=False, dtype=dtype,
                          secure=secure, digits=digits)
        eng.stats.bump("cache_misses")
        _ENGINE_CACHE.put(plan, use_kernel, dtype, eng, secure, digits)
    return eng


def _clique_strides(domain: Domain, clique: Clique) -> Tuple[np.ndarray, int]:
    sizes = [domain.attributes[i].size for i in clique]
    strides = np.ones(len(clique), np.int32)
    for j in range(len(clique) - 2, -1, -1):
        strides[j] = strides[j + 1] * sizes[j + 1]
    return strides, int(np.prod(sizes)) if clique else 1


# Largest one-hot block (records × cells) one histogram step builds: 2^24
# elements, 64 MiB at float32, whatever the record count.
_ONE_HOT_ELEMS = 1 << 24


def _local_marginal(records, cols, strides, n_cells, dtype=None):
    """One-hot histogram of the clique columns (records: (N, n_attrs)).

    ``dtype=None`` resolves to :func:`repro.core.mechanism.noise_dtype` —
    the historical hard-coded float32 default silently capped histogram
    exactness at 2²⁴ counts per cell even when the engine path threaded
    float64 everywhere else.
    """
    return _local_marginals(records, [(cols, strides, n_cells)], dtype)[0]


def _local_marginals(records, metas, dtype=None):
    """One-hot histograms of several cliques, ``metas[i] = (cols, strides,
    n_cells)``, over the same records.

    One scan consumes the records in chunks of ``_ONE_HOT_ELEMS //
    max(n_cells)`` rows and builds every clique's table in its body, so no
    ``(rows, n_cells)`` one-hot grows with N and the program holds one loop
    whatever the clique count (a loop per clique, each with its own chunk
    shape, made the TPU compile grow with N).  Rows past N in the last chunk
    map to index -1, whose one-hot row is all zeros.
    """
    dtype = noise_dtype() if dtype is None else dtype
    n = records.shape[0]
    live = [m for m in metas if len(m[0])]
    if not live:
        return tuple(jnp.asarray([n], dtype) for _ in metas)
    chunk = max(1, min(n, _ONE_HOT_ELEMS // max(m[2] for m in live)))
    n_chunks = -(-n // chunk)
    recs = jnp.pad(records, ((0, n_chunks * chunk - n), (0, 0)))
    recs = recs.reshape(n_chunks, chunk, records.shape[1])

    def step(acc, xs):
        i, rec = xs
        valid = i * chunk + jnp.arange(chunk) < n
        out = []
        for h, (cols, strides, n_cells) in zip(acc, live):
            flat = jnp.zeros((chunk,), jnp.int32)
            for c, s in zip(cols, strides):
                flat = flat + rec[:, c] * int(s)
            flat = jnp.where(valid, flat, -1)
            out.append(h + jnp.sum(jax.nn.one_hot(flat, n_cells, dtype=dtype),
                                   axis=0))
        return tuple(out), None

    hists, _ = jax.lax.scan(
        step, tuple(jnp.zeros((m[2],), dtype) for m in live),
        (jnp.arange(n_chunks), recs))
    hists = iter(hists)
    return tuple(next(hists) if len(m[0]) else jnp.asarray([n], dtype)
                 for m in metas)


def sharded_marginals(domain: Domain, cliques: Sequence[Clique],
                      records: jnp.ndarray, mesh: Optional[Mesh] = None,
                      dtype=None) -> Dict[Clique, jnp.ndarray]:
    """Exact marginal tables for every clique, records sharded over data axes.

    ``dtype=None`` resolves to :func:`repro.core.mechanism.noise_dtype` so the
    tables match the precision of the residual transform consuming them.
    """
    dtype = noise_dtype() if dtype is None else dtype
    cliques = list(cliques)
    outs = sharded_marginals_program(domain, cliques, mesh, dtype)(records)
    return {c: o for c, o in zip(cliques, outs)}


def sharded_marginals_program(domain: Domain, cliques: Sequence[Clique],
                              mesh: Optional[Mesh], dtype):
    """The jitted program behind :func:`sharded_marginals`: records in, one
    table per clique out.  With a mesh it is a shard_map over the mesh's
    data axes whose per-device tables are psum'd (replicated output)."""
    cliques = list(cliques)
    meta = [(_clique_strides(domain, c)) for c in cliques]

    def local(rec):
        return _local_marginals(
            rec, [(list(c),) + m for c, m in zip(cliques, meta)], dtype)

    if mesh is None:
        return jax.jit(local)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    axes = data_axes + tuple(a for a in mesh.axis_names
                             if a not in data_axes)

    def body(rec):
        return tuple(jax.lax.psum(h, axes) for h in local(rec))

    in_spec = P(data_axes, None)
    out_specs = tuple(P() for _ in cliques)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(in_spec,),
                                 out_specs=out_specs, check_vma=False))


def sharded_measure(plan: BasePlan, records: jnp.ndarray,
                    key: jax.Array, mesh: Optional[Mesh] = None,
                    use_kernel: Optional[bool] = None,
                    dtype=None, secure: bool = False,
                    digits: int = 4) -> Dict[Clique, Measurement]:
    """Distributed Algorithms 1/5 (and 3): sharded marginalization + transform.

    ``plan`` is any :class:`~repro.core.plantable.BasePlan` — plain
    :class:`~repro.core.select.Plan` or ResidualPlanner+
    :class:`~repro.core.plus.PlusPlan`; the replicated transform runs on the
    signature-batched engine the plan provides (``plan.engine``), cached per
    (plan, path, dtype, secure).  ``dtype`` governs the marginal tables and
    the noise draws; ``None`` resolves to
    :func:`repro.core.mechanism.noise_dtype` (float64 under jax x64), so the
    distributed path matches the core path's precision.  ``use_kernel=None``
    resolves from the backend (Pallas on a TPU).

    ``secure=True`` serves the numerically secure release (Alg 3) through
    :class:`~repro.engine.discrete_engine.DiscreteEngine`: same sharded
    marginalization, integer-query H/Y† transforms on the fused engine tier,
    exact discrete Gaussian noise seeded deterministically from ``key``
    (``digits`` sets the σ̄ rationalization).  Plans without an integer-query
    rotation (RP+) raise ``ValueError``.
    """
    use_kernel = resolve_use_kernel(use_kernel)
    dtype = noise_dtype() if dtype is None else dtype
    margs = sharded_marginals(plan.domain, plan.cliques, records, mesh,
                              dtype=dtype)
    return _engine_for(plan, use_kernel, dtype, secure, digits).measure(
        margs, key)
