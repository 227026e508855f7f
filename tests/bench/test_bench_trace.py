"""The trace reduction and the per-layer metric readers: interval
arithmetic on made-up intervals, and the whole reduction on a small
trace recorded on a TPU v5e (one BFS over a scale-8 Kronecker graph)."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import harness, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    PER_LAYER = [m["name"] for m in json.load(f)["per_layer"]]


def test_program_name():
    assert trace.program_name("jit__bin_pass_impl(12)") == "_bin_pass_impl"
    assert trace.program_name("jit_compact") == "compact"
    assert trace.program_name("fusion.3") == "fusion.3"


def test_union_and_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [12, 13]], float)
    assert trace.union_length(iv) == 3 + 4 + 1
    assert trace.gaps(iv, 0, 15) == [(3, 5), (9, 12), (13, 15)]
    assert trace.gaps(iv, 1.5, 6) == [(3, 5)]
    assert trace.union_length(np.zeros((0, 2))) == 0.0
    assert trace.gaps(np.zeros((0, 2)), 0, 4) == [(0, 4)]


def _ctx(**kw):
    red = trace.Reduced(window_s=2.0, busy_s=0.5,
                        programs={"_bin_pass_impl": 0.2, "compact": 0.1,
                                  "_host_round_counts": 0.05,
                                  "_lb_pass_impl": 0.1},
                        idle_by_host={"PjitFunction(compact)": 1.5})
    base = dict(trace=red, layers=harness.load_layers(),
                peaks={"hbm_bytes_per_s": 819e9}, traversals=2, rounds=100,
                least_bytes=int(819e9 * 0.03), compiles_in_window=0)
    base.update(kw)
    return harness.MetricContext(**base)


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def test_readers_on_made_up_numbers():
    ctx = _ctx()
    assert _read("device_idle_share", ctx) == pytest.approx(75.0)
    assert _read("host_gap_us_per_round", ctx) == pytest.approx(15000.0)
    assert _read("inspector_ms_per_traversal", ctx) == pytest.approx(75.0)
    assert _read("bin_pass_ms_per_traversal", ctx) == pytest.approx(100.0)
    assert _read("lb_pass_ms_per_traversal", ctx) == pytest.approx(50.0)
    assert _read("edge_pass_roofline", ctx) == pytest.approx(10.0)
    assert _read("compiles_in_window", ctx) == 0


def test_readers_find_nothing_to_read():
    red = trace.Reduced(window_s=1.0, busy_s=0.1,
                        programs={"compact": 0.1}, idle_by_host={})
    ctx = _ctx(trace=red)
    for name in ("bin_pass_ms_per_traversal", "lb_pass_ms_per_traversal",
                 "edge_pass_roofline"):
        assert _read(name, ctx) is None
    assert _read("host_gap_us_per_round", _ctx(rounds=0)) is None


def test_every_per_layer_metric_has_a_reader():
    for name in PER_LAYER:
        assert callable(importlib.import_module(f"bench.metrics.{name}").read)


# recorded on a TPU v5e: the harness's profiler options, three BFS
# traversals of a scale-8 Kronecker graph, each in a traversal span; the
# ``/host:metadata`` plane, which the reduction does not read, removed
RECORDED = os.path.join(DATA, "rmat8_bfs.xplane.pb")
with open(os.path.join(DATA, "rmat8_bfs.json")) as f:
    RECORDED_INFO = json.load(f)


def test_recorded_trace_reduces_to_what_the_chip_run_read():
    assert os.path.getsize(RECORDED) < 1 << 20
    red = trace.reduce_file(RECORDED, harness.TRAVERSAL_SPAN, 1)
    assert red.window_s == pytest.approx(RECORDED_INFO["window_s"])
    assert red.busy_s == pytest.approx(RECORDED_INFO["busy_s"])
    assert red.programs == pytest.approx(RECORDED_INFO["programs"])
    assert 0 < red.busy_s < red.window_s
    assert sum(red.idle_by_host.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_recorded_trace_feeds_every_reader():
    red = trace.reduce_file(RECORDED, harness.TRAVERSAL_SPAN, 1)
    layers = harness.load_layers()
    # a scale-8 graph has no vertex above the LB threshold (1024 edges):
    # every other layer's programs ran
    for name, programs in layers.items():
        assert bool(red.program_seconds(programs)) == (name != "lb_pass")
    ctx = harness.MetricContext(
        trace=red, layers=layers,
        peaks=harness.load_peaks("TPU v5 lite"),
        traversals=len(RECORDED_INFO["rounds"]),
        rounds=sum(RECORDED_INFO["rounds"]),
        least_bytes=RECORDED_INFO["least_bytes"], compiles_in_window=0)
    values = {n: _read(n, ctx) for n in PER_LAYER}
    assert values.pop("lb_pass_ms_per_traversal") is None
    assert all(v is not None for v in values.values()), values
    assert 0 <= values["device_idle_share"] < 100
    assert 0 < values["edge_pass_roofline"] < 100
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_device_missing_from_the_peaks_is_an_error():
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")
