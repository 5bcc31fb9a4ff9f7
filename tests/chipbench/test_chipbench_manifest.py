"""BENCHMARK.json against the rules the driver enforces before any run.

PR 24 was refused, with everything it had built, over the spelling of one
``layer`` field. Every rule of the contract that can be checked without a
chip is checked here, one test case a rule, so that the next manifest error
costs a CPU test and not a PR.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LAYERS = {"train.async_sgd", "models", "parallel.ici", "ops.codec_pallas", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH = re.compile(
    r"((hidden|intermediate|latent|state|proj|projection|head)\w*_(size|dim|width)$"
    r"|_dim$|_rank$|expansion|expand|experts_per_tok)"
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def _cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"] for w in manifest["workloads"]]


def test_top_level_keys_and_size(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths(manifest):
    cmd, paths = manifest["command"], manifest["paths"]
    assert 1 <= len(paths) <= 16 and 1 <= len(cmd) <= 32
    assert set(paths) == {"chipbench", "tests/chipbench"}
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in cmd:
        assert _line(word) and not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word == p or word.startswith(p + "/") for p in paths), word


def test_files_under_paths_are_named_from_name_characters(manifest):
    bad = []
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                if not PATH.match(rel):
                    bad.append(rel)
    assert not bad


def test_run_seconds_fits_the_full_24_cells(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configurations(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    files = [c["file"] for c in configs]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert PATH.match(c["file"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not WIDTH.search(key), key


def test_catalog_configuration_keeps_every_number_of_its_source(manifest):
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        catalog = {e["source_url"]: e for e in map(json.loads, f)}
    seen = 0
    for c in manifest["configs"]:
        entry = catalog.get(c["source"])
        if entry is None:
            continue
        seen += 1
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        for key, value in entry["config"].items():
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                if key in c["reduced"]:
                    assert key in held
                else:
                    assert held.get(key) == value, key
        assert held["reduced"] == c["reduced"]
    assert seen >= 1  # olmoe-layer-table is a catalog model


def test_cells(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_every_cell_has_its_files_and_they_agree_with_the_manifest(manifest):
    for w in manifest["workloads"]:
        path = os.path.join(ROOT, "chipbench", "workloads", w["name"] + ".json")
        assert path.endswith(DATA_SUFFIXES) and os.path.isfile(path), path
        with open(path) as f:
            cell = json.load(f)
        for key in ("name", "config", "traffic", "chips"):
            assert cell[key] == w[key], (w["name"], key)
        assert cell["mesh"][0] * cell["mesh"][1] == w["chips"]
        job = os.path.join(ROOT, "chipbench", "jobs", cell["job"] + ".py")
        assert os.path.isfile(job), job


def test_end_to_end_metrics(manifest):
    metrics = manifest["end_to_end"]
    assert 1 <= len(metrics) <= 16
    cells = {w["name"] for w in manifest["workloads"]}
    for m in metrics:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(_cells_of(m, manifest)) <= cells
    setup = [m for m in metrics if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1 and setup[0]["unit"] == "s"


def test_per_layer_metrics(manifest):
    metrics = manifest["per_layer"]
    assert 1 <= len(metrics) <= 128
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in metrics:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}, m["name"]
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        # the one thing that refused PR 24
        assert NAME.match(m["layer"]) and m["layer"] in LAYERS, m["layer"]
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        mine = set(_cells_of(m, manifest))
        assert mine <= cells
        # the metric it moves is reported in every cell where this one is
        assert mine <= set(_cells_of(e2e[m["moves"]], manifest)), m["name"]
        reader = os.path.join(ROOT, "chipbench", "layer_metrics", m["name"] + ".py")
        assert os.path.isfile(reader), reader
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_no_two_metrics_share_a_name(manifest):
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(names)) == len(names)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(manifest):
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if w["name"] in _cells_of(m, manifest)]
        layer = [m for m in manifest["per_layer"] if w["name"] in _cells_of(m, manifest)]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, w["name"]


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


@pytest.mark.parametrize("bad", ["parallel/ici.py", "sync (ici)", "a b", "", "x" * 65, "-x"])
def test_the_name_pattern_refuses_what_the_driver_refuses(bad):
    assert not NAME.match(bad)
