"""Deployment kinds: the lookup by name, and the ``tables`` kind's requests.

``test_tables_requests_unchanged`` pins what the ``tables`` kind submits.  Its
digests were recorded by running ``_request_digest`` as written here, at the
same sizes and seeds, against the harness as it was before the set-up,
requests and check moved into ``bench/kinds/tables.py`` (when
``harness.run_cell`` built the tables and requests itself): the spy on
``ReleaseServer.submit`` needs nothing of the kind, so the same code read
both sides.
"""
import copy
import hashlib
import json
import shutil
import time

import numpy as np
import pytest

from bench import harness

SEEDS = (2 ** 33 + 7, 9)
DIGESTS = {
    ("adult3.fused4", SEEDS[0]):
        "50916bfe96ebdb9dc1b0a2ef849bff4c9d01f9a55c6c1331fa64a125e954eaa9",
    ("adult3.fused4", SEEDS[1]):
        "475879c11d8c4ed5abde457a5d1ed843f0b223391b2aa242dc0cfe82ae27f507",
    ("synth20.fused16", SEEDS[0]):
        "50916bfe96ebdb9dc1b0a2ef849bff4c9d01f9a55c6c1331fa64a125e954eaa9",
    ("synth20.fused16", SEEDS[1]):
        "475879c11d8c4ed5abde457a5d1ed843f0b223391b2aa242dc0cfe82ae27f507",
    ("synth20.solo", SEEDS[0]):
        "504fe4d300990b105c3f1aa2bea3dc4dc7fb6e5f159a9f8095bb05d3aa41c087",
    ("synth20.solo", SEEDS[1]):
        "c1704e6b8248b4de39ca537bd8de6523213ebd849d1aadd515865cab300258be",
}
MIXES = {"adult3.fused4": {}, "synth20.fused16": {"tenants": 4, "max_batch": 4},
         "synth20.solo": {}}


def _tiny(cell):
    """The tiny size of test_rehearse.py."""
    cell = copy.deepcopy(cell)
    cell.config.update(sizes=[4, 3, 5, 2, 3, 2], records_per_tenant=500,
                       records_per_release=8)
    return cell


def _run(cell, seed, tmp_path, seconds=0.3):
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            t_start=time.monotonic(), require_tpu=False,
                            workdir=str(tmp_path))


def _request_digest(cell, seed, tmp_path, monkeypatch):
    """sha256 over each tenant's first 3 releases as submitted: tenant name,
    noise seed and the float32 bytes of every table in the request's order.
    A release is told by its table's total count: base records + (r + 1)
    fresh batches."""
    from repro.serve import ReleaseServer
    seen = []
    real = ReleaseServer.submit

    def spy(self, req):
        tabs = [np.asarray(m, np.float32) for m in req.marginals.values()]
        seen.append((req.tenant, float(req.marginals[()][0]), int(req.seed),
                     b"".join(t.tobytes() for t in tabs)))
        return real(self, req)

    monkeypatch.setattr(ReleaseServer, "submit", spy)
    out = _run(cell, seed, tmp_path)
    monkeypatch.undo()
    assert out["correct"], out["checks"]
    per = {}
    for tenant, total, nseed, raw in seen:
        per.setdefault(tenant, []).append((total, nseed, raw))
    base = cell.config["records_per_tenant"]
    batch = cell.config["records_per_release"]
    h = hashlib.sha256()
    for tenant in sorted(per):
        first = sorted(per[tenant])[:3]
        assert [f[0] for f in first] == [base + (r + 1) * batch
                                         for r in range(3)]
        for _total, nseed, raw in first:
            h.update(tenant.encode())
            h.update(nseed.to_bytes(8, "little"))
            h.update(raw)
    return h.hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(MIXES))
def test_tables_requests_unchanged(name, seed, tmp_path, monkeypatch):
    cell = _tiny(harness.load_cell(name))
    cell.mix.update(MIXES[name])
    assert _request_digest(cell, seed, tmp_path, monkeypatch) == \
        DIGESTS[(name, seed)]


def _root(tmp_path, kind):
    """A checkout root in ``tmp_path``: BENCHMARK.json, the configuration
    files and the kinds, with ``adult3``'s ``deployment_kind`` set to
    ``kind`` (``None``: the key left out)."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(harness.BENCH / "kinds", tmp_path / "bench" / "kinds",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for c in spec["configs"]:
        dst = tmp_path / c["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(harness.ROOT / c["file"], dst)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    path = tmp_path / "bench" / "configs" / "adult3.json"
    config = json.loads(path.read_text())
    del config["deployment_kind"]
    if kind is not None:
        config["deployment_kind"] = kind
    path.write_text(json.dumps(config))
    return tmp_path


@pytest.mark.parametrize("kind", [None, "no_such_kind", "../kinds/tables", 7])
def test_unknown_kind_is_refused(kind, tmp_path):
    root = _root(tmp_path, kind)
    with pytest.raises(harness.CellError):
        harness.load_cell("adult3.fused4", root=root)
    # The other configuration still names a kind that exists.
    assert harness.load_cell("synth20.solo", root=root).config[
        "deployment_kind"] == "tables"


MIRROR = '''
"""Client tables, with a check item of the kind's own."""
from bench.kinds import tables


class Deployment(tables.Deployment):
    checked = []

    def next_request(self, t):
        r, req, (seed, buf) = super().next_request(t)
        return r, req, {"tenant": req.tenant, "release": r, "seed": seed,
                        "tables": buf}

    def check(self, sample, journal, submitted):
        Deployment.checked.extend(item for item, _res in sample)
        return super().check([((i["seed"], i["tables"]), res)
                              for i, res in sample], journal, submitted)
'''


def test_new_kind_is_new_files(tmp_path):
    root = _root(tmp_path / "root", "mirror")
    (root / "bench" / "kinds" / "mirror.py").write_text(MIRROR)
    cell = _tiny(harness.load_cell("adult3.fused4", root=root))
    cell.mix.update(check_sample=10 ** 6)
    out = _run(cell, SEEDS[1], tmp_path, seconds=1.0)
    assert out["correct"], out["checks"]
    items = cell.kind.checked
    assert len(items) == out["run"]["checked_releases"] > 0
    assert {i["tenant"] for i in items} <= {f"tenant-{t}" for t in range(4)}
