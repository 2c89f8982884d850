"""BENCHMARK.json keeps to the benchmark's contract, and every entry
resolves to the files the harness reads."""
import json
import re

import pytest

import run
from archs import CONTRACT
from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# Keys that size a width (hidden, intermediate, latent, state, projection,
# head or window sizes, expansion factors, experts per token), which
# ``reduced`` may never name; depth, expert and vocabulary counts it may.
WIDTHS = re.compile(r"(hidden_size|intermediate|latent|state|projection|"
                    r"head|window|_dim$|_rank$|expan|experts_per_tok)")


def _line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_time_budget_fits_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_resolves(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and _line_ok(entry["why"])
    assert _line_ok(entry["source"])
    path = ROOT / entry["file"]
    assert path.is_file() and entry["file"].startswith("bench/")
    conf = json.loads(path.read_text())
    assert conf["name"] == entry["name"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    changed = sorted(k for k in conf["published"]
                     if conf["used"].get(k) != conf["published"][k])
    assert changed == sorted(entry["reduced"])
    assert not any(WIDTHS.search(k) for k in entry["reduced"])
    assert (BENCH / "archs" / f"{conf['harness']}.py").is_file()
    mod = run.load_harness(conf, entry["file"])
    assert all(callable(getattr(mod, n)) for n in CONTRACT)
    assert len(entry["reduced"]) <= 16
    assert all(NAME.match(k) for k in entry["reduced"])
    assert (BENCH / "profiles" / f"{entry['name']}.jsonl").is_file()
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])
    limits = conf["limits"]
    assert set(limits) == {"chip", "rehearsal"}


@pytest.mark.parametrize("key,width", [
    ("num_hidden_layers", False), ("num_experts", False),
    ("n_routed_experts", False), ("vocab_size", False),
    ("tie_word_embeddings", False),
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("kv_lora_rank", True), ("mamba_d_state", True), ("mamba_expand", True),
    ("num_experts_per_tok", True), ("sliding_window", True),
])
def test_reduced_may_cut_depth_and_counts_but_no_width(key, width):
    assert bool(WIDTHS.search(key)) is width


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert _line_ok(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    assert mix["mode"] == "pd"
    assert mix["arrivals"]["knee_rps"] > 0


def test_cells_unique_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(set(names)) == len(names)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(len(SPEC["workloads"]) // 2, 1)


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert set(e2e) == {"ttft_p95_s", "ttft_p50_s", "tpot_p95_s",
                        "tok_per_s", "setup_s"}
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_resolves(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in SOURCES and _line_ok(metric["layer"])
    assert metric["better"] in ("lower", "higher")
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    reader = BENCH / "metrics" / f"{metric['name']}.py"
    assert reader.is_file()
    assert "def read(ctx)" in reader.read_text()
