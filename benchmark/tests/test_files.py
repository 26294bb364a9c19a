"""BENCHMARK.json against the contract, and every file it names."""

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"]
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names.append(w["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def _cells_of(metric):
    return set(metric.get("workloads", [w["name"] for w in BENCH["workloads"]]))


def test_every_cell_reports_and_every_arrow_lands():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        mine = [m for m in BENCH["end_to_end"] if w["name"] in _cells_of(m)]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any(w["name"] in _cells_of(m) for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        assert _cells_of(m) <= _cells_of(e2e[m["moves"]]), m["name"]


def test_every_named_file_exists_and_loads():
    used = set()
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "benchmark" / "reference" / f"{cfg['reference']}.py").exists()
        view = importlib.import_module(
            f"benchmark.views.{cfg.get('view', 'dense')}")
        assert callable(view.view)
        seeding = getattr(view, "seeding", None)
        assert seeding is None or callable(seeding)
    for w in BENCH["workloads"]:
        used.add(w["config"])
        assert (ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json").exists()
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"]:
        assert (ROOT / "benchmark" / "e2e_metrics" / f"{m['name']}.json").exists()
    for m in BENCH["per_layer"]:
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{m['name']}.json").read_text())
        mod = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        assert callable(mod.read)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_published_sizes_and_the_programs_config_agree(name):
    c = next(c for c in BENCH["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / c["file"]).read_text())
    dc = cfg["program"]["decoder_config"]
    if cfg["model_type"] == "gpt2":
        pairs = [("n_layer", "num_layers"), ("n_embd", "d_model"),
                 ("n_head", "num_heads"), ("n_head", "num_kv_heads"),
                 ("vocab_size", "vocab_size"), ("n_positions", "max_seq_len")]
        assert dc["mlp_dim"] == 4 * cfg["n_embd"]
    else:
        # A file with routed experts: ``mlp_dim`` is ONE expert's width and
        # ``intermediate_size`` the leading dense layers', where it has any.
        experts = "moe_intermediate_size" in cfg
        pairs = [("num_hidden_layers", "num_layers"), ("hidden_size", "d_model"),
                 ("num_attention_heads", "num_heads"),
                 ("num_key_value_heads", "num_kv_heads"),
                 ("moe_intermediate_size" if experts else "intermediate_size",
                  "mlp_dim"),
                 ("vocab_size", "vocab_size")]
        if "dense_mlp_dim" in dc:
            pairs.append(("intermediate_size", "dense_mlp_dim"))
        rope = cfg.get("rope_parameters", cfg)     # nested in newer files
        assert rope["rope_theta"] == dc["rope_theta"]
    for pub, prog in pairs:
        assert cfg[pub] == dc[prog], (pub, prog)


def test_open_loop_traffic_states_its_sample_size():
    for w in BENCH["workloads"]:
        t = json.loads((ROOT / "benchmark" / "traffic"
                        / f"{w['traffic']}.json").read_text())
        if t["loop"] != "open":
            continue
        n = round(t["arrivals"]["rate_rps"] * BENCH["run_seconds"])
        assert t["parts"] % 2 == 1 and t["parts"] >= 3
        # ten requests beyond the p90 of the whole window, and the count said
        assert n >= 100 and str(n) in t["requests"], w["name"]


def test_no_file_of_the_benchmark_is_unused():
    """Every data file and reader is named by BENCHMARK.json or by a file
    it names: nothing dead rides along."""
    bench = ROOT / "benchmark"
    readers = set()
    for m in BENCH["per_layer"]:
        readers.add(json.loads((bench / "layer_metrics" / f"{m['name']}.json")
                               .read_text())["reader"])
    assert {p.stem for p in (bench / "readers").glob("*.py")} - {"__init__"} == readers
    assert ({p.name[:-5] for p in (bench / "layer_metrics").glob("*.json")}
            == {m["name"] for m in BENCH["per_layer"]})
    assert ({p.name[:-5] for p in (bench / "e2e_metrics").glob("*.json")}
            == {m["name"] for m in BENCH["end_to_end"]})
    assert ({p.stem for p in (bench / "traffic").glob("*.json")}
            == {w["traffic"] for w in BENCH["workloads"]})
    assert ({f"benchmark/configs/{p.name}" for p in (bench / "configs").glob("*.json")}
            == {c["file"] for c in BENCH["configs"]})
    named = [json.loads((ROOT / c["file"]).read_text()) for c in BENCH["configs"]]
    assert ({p.stem for p in (bench / "views").glob("*.py")} - {"__init__"}
            == {cfg.get("view", "dense") for cfg in named})
    assert ({p.stem for p in (bench / "reference").glob("*.py")} - {"__init__"}
            == {cfg["reference"] for cfg in named})
