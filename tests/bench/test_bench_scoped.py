"""The trace's op metadata and round spans (``bench/xspace.py``) and the
readers of the per-layer metrics that split the edge passes and the
host gap, and that read the program's slot counters: on made-up
numbers, against TensorFlow's own XSpace parser, and on a trace
recorded on a TPU v5e with the scoped program (three BFS traversals of
a scale-8 Kronecker graph)."""
import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import harness, trace, xspace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "rmat8_bfs.xplane.pb")
SCOPED = os.path.join(DATA, "rmat8_bfs_scoped.xplane.pb")
with open(os.path.join(DATA, "rmat8_bfs_scoped.json")) as f:
    SCOPED_INFO = json.load(f)
NEW = ("bin_slot_efficiency", "lb_slot_efficiency",
       "edge_gather_ms_per_traversal", "edge_scatter_ms_per_traversal",
       "sync_gap_us_per_round", "dispatch_gap_us_per_round")


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def test_op_scope_and_span_name():
    assert xspace.op_scope("jit(_bin_pass_impl)/combine/scatter-min:") \
        == "combine"
    assert xspace.op_scope(
        "jit(_lb_pass_impl)/enumerate/jit(prefix_sum)/add:") == "enumerate"
    assert xspace.op_scope("jit(_bin_pass_impl)/jit(_where)/select_n:") \
        == ""
    # the op itself is never its own scope
    assert xspace.op_scope("jit(f)/edges:") == ""
    assert xspace.op_scope("") == ""
    assert xspace.span_name("graph.round#round=3#") == "graph.round"
    assert xspace.span_name("graph.counts") == "graph.counts"


def test_idle_gaps_go_to_the_innermost_round_span():
    """The round spans nest, and the reduction puts each gap down to
    the innermost one around its midpoint, "other" outside them all."""
    spans = [("graph.round", 0, 100), ("graph.counts", 0, 30),
             ("graph.plan", 30, 50), ("graph.passes", 50, 90),
             ("graph.update", 95, 100), ("graph.round", 120, 140),
             ("graph.counts", 120, 140)]
    idle = [(10, 20), (40, 45), (91, 93), (96, 99), (200, 210),
            (125, 127)]
    assert trace._attribute(idle, spans) == {
        "graph.counts": 10 + 2, "graph.plan": 5, "graph.round": 2,
        "graph.update": 3, "other": 10}


def _ctx(counters=None, scopes=None, idle=None, rounds=10):
    red = types.SimpleNamespace(scopes=scopes, idle_by_span=idle)
    return types.SimpleNamespace(trace=red, counters=counters,
                                 layers=harness.load_layers(),
                                 traversals=2, rounds=rounds)


def test_new_readers_on_made_up_numbers():
    ctx = _ctx(counters={"bin_slots": 400, "bin_edges": 100,
                         "lb_slots": 64, "lb_edges": 48},
               scopes={"_bin_pass_impl": {"edges": 0.3, "sources": 0.1,
                                          "combine": 0.5, "": 0.1},
                       "_lb_pass_impl": {"enumerate": 0.2, "edges": 0.1,
                                         "sources": 0.1, "combine": 0.3},
                       "compact": {"": 1.0}},
               idle={"graph.counts": 0.02, "graph.plan": 0.003,
                     "graph.passes": 0.004, "graph.update": 0.001,
                     "graph.round": 0.5, "other": 7.0})
    assert _read("bin_slot_efficiency", ctx) == pytest.approx(25.0)
    assert _read("lb_slot_efficiency", ctx) == pytest.approx(75.0)
    assert _read("edge_gather_ms_per_traversal", ctx) == pytest.approx(300.0)
    assert _read("edge_scatter_ms_per_traversal", ctx) == \
        pytest.approx(400.0)
    assert _read("sync_gap_us_per_round", ctx) == pytest.approx(2000.0)
    assert _read("dispatch_gap_us_per_round", ctx) == pytest.approx(800.0)


def test_new_readers_find_nothing_to_read():
    # what the harness's context holds without the new fields: no
    # counters, a reduced trace without scopes or round spans
    red = trace.Reduced(window_s=1.0, busy_s=0.5,
                        programs={"_bin_pass_impl": 0.5}, idle_by_host={})
    ctx = harness.MetricContext(
        trace=red, layers=harness.load_layers(), peaks={}, traversals=1,
        rounds=7, least_bytes=0, compiles_in_window=0)
    for name in NEW:
        assert _read(name, ctx) is None, name
    # a graph on which the LB pass never fires, programs that ran
    # without the scopes, a round loop without the counts span
    ctx = _ctx(counters={"bin_slots": 8, "bin_edges": 4, "lb_slots": 0,
                         "lb_edges": 0},
               scopes={"_bin_pass_impl": {"": 0.5}},
               idle={"graph.plan": 0.1})
    assert _read("lb_slot_efficiency", ctx) is None
    assert _read("edge_gather_ms_per_traversal", ctx) is None
    assert _read("edge_scatter_ms_per_traversal", ctx) is None
    assert _read("sync_gap_us_per_round", ctx) is None
    assert _read("dispatch_gap_us_per_round", _ctx(idle={})) is None
    assert _read("sync_gap_us_per_round",
                 _ctx(idle={"graph.counts": 1.0}, rounds=0)) is None


# TensorFlow's parser of the same file, in a process of its own (the
# import takes seconds and loads a second runtime): every plane's
# events as [name, start ps, duration ps, stats]
_TF_DUMP = r"""
import json, sys
from tensorflow.tsl.profiler.protobuf import xplane_pb2
space = xplane_pb2.XSpace()
with open(sys.argv[1], "rb") as f:
    space.ParseFromString(f.read())

def value(stat, names):
    kind = stat.WhichOneof("value")
    v = getattr(stat, kind) if kind else None
    if kind == "ref_value":
        return names.get(v, "")
    return v.hex() if isinstance(v, bytes) else v

out = []
for plane in space.planes:
    names = {k: m.name for k, m in plane.stat_metadata.items()}
    lines = {}
    for line in plane.lines:
        events = []
        for e in line.events:
            md = plane.event_metadata[e.metadata_id]
            stats = {names.get(s.metadata_id, str(s.metadata_id)):
                     value(s, names) for s in md.stats}
            stats.update({names.get(s.metadata_id, str(s.metadata_id)):
                          value(s, names) for s in e.stats})
            events.append([md.name, line.timestamp_ns * 1000 + e.offset_ps,
                           e.duration_ps, stats])
        lines[line.name] = events
    out.append([plane.name, lines])
json.dump(out, sys.stdout)
"""


@pytest.mark.parametrize("path", [OLD, SCOPED],
                         ids=["rmat8_bfs", "rmat8_bfs_scoped"])
def test_reader_agrees_with_tensorflow(path):
    env = dict(os.environ, TF_CPP_MIN_LOG_LEVEL="3")
    proc = subprocess.run([sys.executable, "-c", _TF_DUMP, path],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = json.loads(proc.stdout)
    got = xspace.read_planes(path)
    assert [p.name for p in got] == [name for name, _ in want]
    for plane, (_, lines) in zip(got, want):
        assert list(plane.lines) == list(lines)
        for name, events in lines.items():
            mine = plane.lines[name]
            assert [e.name for e in mine] == [e[0] for e in events]
            np.testing.assert_allclose(
                [e.start_ns for e in mine], [e[1] * 1e-3 for e in events],
                rtol=0, atol=1e-3)
            np.testing.assert_allclose(
                [e.end_ns - e.start_ns for e in mine],
                [e[2] * 1e-3 for e in events], rtol=0, atol=1e-3)
            for e, (_, _, _, stats) in zip(mine, events):
                assert {k: v.hex() if isinstance(v, bytes) else v
                        for k, v in e.stats.items()} == stats


def test_scoped_reduction_of_the_older_trace():
    """The PR 14 trace, recorded before the scopes and spans: every op
    falls outside a scope, every idle gap outside a round span, and the
    per-program totals and the idle time are the reduction's own."""
    sc = xspace.reduce_file(OLD, harness.TRAVERSAL_SPAN, 1)
    red = trace.reduce_file(OLD, harness.TRAVERSAL_SPAN, 1)
    assert sc.window_s == pytest.approx(red.window_s)
    assert set(sc.idle_by_span) == {"other"}
    assert sc.idle_by_span["other"] == pytest.approx(
        red.window_s - red.busy_s, rel=1e-4)
    assert sc.round_spans == 0
    assert all(set(by) == {""} for by in sc.scopes.values())
    assert sum(sc.scopes["_bin_pass_impl"].values()) == pytest.approx(
        red.programs["_bin_pass_impl"], rel=0.01)


def test_recorded_scoped_trace_reduces_to_what_the_chip_run_read():
    assert os.path.getsize(SCOPED) < 1 << 20
    sc = xspace.reduce_file(SCOPED, harness.TRAVERSAL_SPAN, 1)
    red = trace.reduce_file(SCOPED, harness.TRAVERSAL_SPAN, 1)
    info = SCOPED_INFO
    assert red.window_s == pytest.approx(info["window_s"])
    assert red.busy_s == pytest.approx(info["busy_s"])
    assert red.programs == pytest.approx(info["programs"])
    assert sc.window_s == pytest.approx(info["window_s"])
    assert sc.round_spans == info["round_spans"]
    assert sc.round_spans == sum(info["rounds"]) + len(info["rounds"])
    assert set(sc.scopes) == set(info["scopes"])
    for program, by in info["scopes"].items():
        assert sc.scopes[program] == pytest.approx(by), program
    assert sc.idle_by_span == pytest.approx(info["idle_by_span"])
    # every op of the bin pass program ran under one of the scopes but
    # for a sliver of copies; the idle time of each round sits under one
    # of its phases, not under the round's own span
    bin_ops = sc.scopes["_bin_pass_impl"]
    assert sum(bin_ops[s] for s in ("edges", "sources", "combine")) \
        >= 0.9 * sum(bin_ops.values())
    assert "enumerate" not in bin_ops
    in_rounds = sum(v for k, v in sc.idle_by_span.items() if k != "other")
    assert sc.idle_by_span.get("graph.round", 0) <= 0.1 * in_rounds
    assert sum(sc.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-4)


def test_recorded_scoped_trace_feeds_every_reader():
    """All thirteen readers on the scoped trace, with the counter
    deltas the chip run took around its traced traversals."""
    red = trace.reduce_file(SCOPED, harness.TRAVERSAL_SPAN, 1)
    sc = xspace.reduce_file(SCOPED, harness.TRAVERSAL_SPAN, 1)
    red.scopes, red.idle_by_span = sc.scopes, sc.idle_by_span
    info = SCOPED_INFO
    ctx = harness.MetricContext(
        trace=red, layers=harness.load_layers(),
        peaks=harness.load_peaks("TPU v5 lite"),
        traversals=len(info["rounds"]), rounds=sum(info["rounds"]),
        least_bytes=info["least_bytes"], compiles_in_window=0)
    ctx.counters = info["counters"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    values = {n: _read(n, ctx) for n in names + list(NEW)}
    assert len(values) == 13
    # a scale-8 graph has no vertex above the LB threshold (1024 edges)
    assert values.pop("lb_pass_ms_per_traversal") is None
    assert values.pop("lb_slot_efficiency") is None
    assert all(v is not None for v in values.values()), values
    assert values == pytest.approx(info["metrics"])
    assert 0 < values["bin_slot_efficiency"] < 100
    c = info["counters"]
    assert c["bin_edges"] + c["lb_edges"] == info["scanned_edges"]
    assert values["edge_gather_ms_per_traversal"] \
        + values["edge_scatter_ms_per_traversal"] \
        <= values["bin_pass_ms_per_traversal"]
    assert values["sync_gap_us_per_round"] \
        + values["dispatch_gap_us_per_round"] \
        <= values["host_gap_us_per_round"]
