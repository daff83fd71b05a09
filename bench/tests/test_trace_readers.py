"""The readers of the transfer, embedding and collection spans: each gives
the hand-computed number on a synthetic span list, nothing on spans of a
program that lacks those spans, and all six in a traced rehearsal."""
import copy
import time

import pytest

from bench import harness, layers

NEW = ("embed_s", "dispatch_s", "fetch_s", "h2d_mb", "d2h_mb", "gc_s")


def _span(name, t0, t1, **attrs):
    return {"trace": "t", "span": f"{name}@{t0}", "parent": None,
            "name": name, "t0": t0, "t1": t1, "dur_us": (t1 - t0) * 1e6,
            "attrs": attrs}


def _ctx(spans):
    return layers.Context(cell=None, spans=spans, t_open=0.0, t_close=100.0,
                          compiles_in_window=0, plan_groups={},
                          chain_fused={}, profile=None, device_kind="cpu")


# Two whole batches, drained at 0.5 and 4.0 (the next drain, 6.5, ends
# the second): a fused measurement with a per-axis stage, then two
# reconstructions; a release, two collections.  A third batch that the
# window cuts (drained at 6.5, the window closes at 100), and a span outside
# the window, are not read.
SPANS = [
    _span("serve.queue_wait", 0.1, 0.5, batch_size=1),
    _span("serve.queue_wait", 0.2, 4.0, batch_size=1),
    _span("serve.queue_wait", 4.1, 6.5, batch_size=1),
    _span("engine.reconstruct", 6.5, 7.0),
    _span("reconstruct.embed", 6.5, 6.9, dims="3", rows=1),
    _span("python.gc", 6.6, 6.7, generation=0, collected=1),
    _span("measure.multi.dispatch", 1.0, 1.25, stage="flat", bytes=3_000_000),
    _span("measure.multi.fetch", 1.25, 1.5, stage="flat", bytes=1_000_000),
    _span("measure.multi.dispatch", 1.5, 1.75, stage="nd", bytes=500_000),
    _span("measure.multi.fetch", 1.75, 2.0, stage="nd", bytes=250_000),
    _span("engine.reconstruct", 2.0, 4.0),
    _span("reconstruct.embed", 2.0, 2.5, dims="3x4", rows=2),
    _span("reconstruct.embed", 2.5, 3.0, dims="3", rows=1),
    _span("reconstruct.dispatch", 3.0, 3.5, bytes=2_000_000),
    _span("reconstruct.fetch", 3.5, 4.0, bytes=2_000_000),
    _span("engine.reconstruct", 4.0, 6.0),
    _span("reconstruct.embed", 4.0, 5.0, dims="3x4", rows=2),
    _span("reconstruct.dispatch", 5.0, 5.5, bytes=2_000_000),
    _span("reconstruct.fetch", 5.5, 6.0, bytes=2_000_000),
    _span("python.gc", 2.1, 2.2, generation=0, collected=3),
    _span("python.gc", 5.6, 5.9, generation=2, collected=40),
    _span("reconstruct.embed", 99.5, 101.0, dims="3", rows=1),
]
WANT = {
    "embed_s": (0.5 + 0.5 + 1.0) / 2,
    "dispatch_s": (0.25 + 0.25 + 0.5 + 0.5) / 2,
    "fetch_s": (0.25 + 0.25 + 0.5 + 0.5) / 2,
    "h2d_mb": (3.0 + 0.5 + 2.0 + 2.0) / 2,
    "d2h_mb": (1.0 + 0.25 + 2.0 + 2.0) / 2,
    "gc_s": (0.1 + 0.3) / 2,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_hand_computed(name):
    got = harness.metric_reader(name)(_ctx(SPANS))
    assert got == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_reader_silent_without_the_spans(name):
    """The parent program records engine.reconstruct and none of these."""
    spans = [s for s in SPANS
             if s["name"] in ("engine.reconstruct", "serve.queue_wait")]
    assert harness.metric_reader(name)(_ctx(spans)) is None


def test_gc_reads_zero_without_a_collection():
    spans = [s for s in SPANS if s["name"] != "python.gc"]
    assert harness.metric_reader("gc_s")(_ctx(spans)) == 0.0


@pytest.mark.parametrize("name,mix", [
    ("adult3.fused4", {}),
    ("synth20.fused16", {"tenants": 4, "max_batch": 4}),
    ("synth20.solo", {"profile_batches": 3}),
])
def test_traced_rehearsal_reports_all_six(name, mix, tmp_path):
    cell = copy.deepcopy(harness.load_cell(name))
    cell.config.update(sizes=[4, 3, 5, 2, 3, 2], records_per_tenant=500,
                       records_per_release=8)
    cell.mix.update(mix)
    out = harness.run_cell(cell, seed=2 ** 33 + 11, seconds=1.5, trace=True,
                           t_start=time.monotonic(), require_tpu=False,
                           workdir=str(tmp_path))
    assert out["correct"], out["checks"]
    got = {k: out["metrics"][k]["value"] for k in NEW}
    assert all(v >= 0 for v in got.values()), got
    assert got["h2d_mb"] > 0 and got["d2h_mb"] > 0 and got["embed_s"] > 0
