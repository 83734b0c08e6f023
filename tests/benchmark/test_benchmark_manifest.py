"""BENCHMARK.json against the files it names, and against the contract's
rules that a file can break without a run."""

import json
import os
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEY = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|latent"
                       r"|state_size|expansion|experts_per_tok|^width$")


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(manifest.ROOT, path))
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_names_units_and_keys(bench):
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for metric in bench[group]:
            assert set(metric) - {"workloads"} == keys, metric
            assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
            assert metric["better"] in ("lower", "higher")
            assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for entry in bench["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(entry[k]) for k in ("name", "config",
                                                  "traffic"))
        assert entry["chips"] in (1, 4)
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_at_most_one_cell_asks_for_four_chips(bench):
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_every_file_resolves_by_name(bench):
    for name in _cells(bench):
        cell = manifest.cell(name, bench)
        assert cell["traffic"]["chips"] == cell["chips"]
        assert hasattr(manifest.load_job(cell["config"]["job"]), "build")
        assert hasattr(manifest.load_reference(cell["config"]["reference"]),
                       "loss_and_grads")
        for metric in cell["end_to_end"] + cell["per_layer"]:
            assert callable(manifest.load_reader(metric["name"]))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for table in manifest.load_json(
            os.path.join(manifest.HERE, "peaks.json")).values():
        assert {"bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes",
                "source"} <= set(table)


def test_every_cell_reports_what_the_contract_asks(bench):
    for name in _cells(bench):
        cell = manifest.cell(name, bench)
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        for metric in cell["per_layer"]:
            assert metric["moves"] in reported, (name, metric["name"])
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all(1 <= len(layer) <= 200 for layer in layers)


def test_reduced_lists_every_changed_key_and_no_width(bench):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = {}
    if os.path.exists(catalog):
        with open(catalog) as f:
            for line in f:
                row = json.loads(line)
                published[row["source_url"]] = row["config"]
    for config in bench["configs"]:
        assert len(config["reduced"]) <= 16
        assert not any(WIDTH_KEY.search(key) for key in config["reduced"])
        run_as = manifest.load_json(
            os.path.join(manifest.ROOT, config["file"]))
        assert run_as["reduced"] == config["reduced"]
        assert run_as["source"] == config["source"]
        for key, value in published.get(config["source"], {}).items():
            if key not in config["reduced"]:
                assert run_as[key] == value, (config["name"], key)
            elif isinstance(value, (int, float)):
                assert run_as[key] != value, (config["name"], key)


def test_the_entry_module_names_no_cell_file_or_metric(bench):
    with open(os.path.join(manifest.HERE, "run.py")) as f:
        text = f.read()
    names = ([m["name"] for m in bench["per_layer"] + bench["end_to_end"]]
             + [c["name"] for c in bench["configs"]]
             + [w["traffic"] for w in bench["workloads"]])
    # The set-up's phases and its total are the harness's own words.
    own = {"setup_s", "import_s", "backend_s", "state_s", "compile_s",
           "warmup_s", "cache_misses_in_setup", "compiles_in_window"}
    for name in set(names) - own:
        assert name not in text, name
