"""``BENCHMARK.json`` and the files it names: the format's keys and
character sets, every name resolving to its file, every layer's
programs existing in the engine, and a run that finds no TPU failing
with no result."""
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def one_line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_size():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    assert cmd[1] == "bench/run.py"
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_names_units_and_keys():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in METRICS])
    for kind in ("configs", "workloads"):
        listed = [e["name"] for e in SPEC[kind]]
        assert len(listed) == len(set(listed))
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and one_line(w["why"])
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"])
        assert m["moves"] in e2e
        listed = m.get("workloads", sorted(cells))
        assert set(listed) <= cells
        # every cell the metric lists reports the metric it moves
        for cell in listed:
            assert cell in e2e[m["moves"]].get("workloads", cells)
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    # one layer name per layer file
    layers = {}
    for f in glob.glob(os.path.join(BENCH, "layers", "*.json")):
        with open(f) as fh:
            layers[json.load(fh)["layer"]] = f
    named = {m["layer"] for m in SPEC["per_layer"]}
    assert set(layers) <= named


def test_every_cell_resolves():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    assert len({(w["config"], w["traffic"])
                for w in SPEC["workloads"]}) == len(SPEC["workloads"])
    fours = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert fours <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        c = configs[w["config"]]
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"] and config["chips"] == w["chips"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert set(config["reduced"]) <= set(config["source_values"])
        assert os.path.isfile(os.path.join(
            BENCH, "generators", config["generator"] + ".py"))
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(
            BENCH, "samplers", traffic["roots"]["sampler"] + ".py"))


def test_run_seconds_fit_the_check():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # the full check with 24 cells fits its 43,200 seconds
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peaks_name_the_chip():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and "Google" in v5e["source"]


@pytest.mark.parametrize("layer_file", sorted(
    os.path.basename(f) for f in glob.glob(os.path.join(BENCH, "layers",
                                                         "*.json"))))
def test_layer_programs_exist_in_the_engine(layer_file):
    """Each program a layer reads is a function the engine jits: a
    rename shows here, not as a layer that silently reads nothing."""
    with open(os.path.join(BENCH, "layers", layer_file)) as f:
        layer = json.load(f)
    text = ""
    for path in glob.glob(os.path.join(ROOT, "src", "repro", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            text += f.read()
    for name in layer["programs"]:
        # a program jitted through functools.partial is named _unknown
        defined = layer.get("jitted_as", {}).get(name, name)
        assert re.search(rf"^\s*def {re.escape(defined)}\(", text, re.M), name
        if defined != name:
            assert re.search(rf"jax\.jit\(partial\({re.escape(defined)}\b",
                             text), defined


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_run_without_a_tpu_fails_with_no_result(cell):
    p = _run(ROOT, "--workload", cell, "--seed", str(2 ** 31 + 5),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "correct" not in p.stdout and "metrics" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's paths
    holds no system to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = SPEC["workloads"][0]["name"]
    p = _run(tmp_path, "--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0 and "correct" not in p.stdout
