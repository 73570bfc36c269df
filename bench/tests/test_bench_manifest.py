"""BENCHMARK.json against the contract's static rules, and the harness
finding a new configuration, traffic mix and per-layer metric by name."""
import copy
import json

import pytest

from bench.harness import loader, manifest


@pytest.fixture
def m():
    return manifest.load()


def test_benchmark_json_meets_the_rules(m):
    assert manifest.validate(m) == []


def test_every_cell_has_its_files(m):
    for w in m["workloads"]:
        cfg = loader.config(manifest.config_entry(m, w["config"]))
        loader.driver(cfg["driver"])
        loader.traffic(w["traffic"])
        assert loader.limits(w["name"])["numbers"]
        for met in manifest.per_layer_for(m, w["name"]):
            assert callable(loader.metric_reader(met["name"]).read)


@pytest.mark.parametrize("bad_name", ["has space", "comma,x", "a/b", "x" * 65,
                                      "-lead", "µs"])
def test_name_characters(m, bad_name):
    m = copy.deepcopy(m)
    m["per_layer"][0]["name"] = bad_name
    assert any("name" in e for e in manifest.validate(m))


@pytest.mark.parametrize("bad_unit", ["tokens per s", "µs", "", "x" * 17])
def test_unit_characters(m, bad_unit):
    m = copy.deepcopy(m)
    m["end_to_end"][0]["unit"] = bad_unit
    assert any("unit" in e for e in manifest.validate(m))


def test_moves_must_be_reported_by_the_same_cells(m):
    m = copy.deepcopy(m)
    met = next(x for x in m["per_layer"] if x["moves"] == "ttft_ms")
    met["workloads"] = [m["workloads"][0]["name"]]     # a training cell
    assert any("does not report" in e for e in manifest.validate(m))


def test_at_most_half_the_cells_on_four_chips(m):
    m = copy.deepcopy(m)
    for w in m["workloads"]:
        w["chips"] = 4
    assert any("4 chips" in e for e in manifest.validate(m))


def test_shares_of_a_peak_are_percent(m):
    m = copy.deepcopy(m)
    next(x for x in m["per_layer"] if "roofline" in x["name"])["unit"] = "share"
    assert any("in %" in e for e in manifest.validate(m))


def test_setup_bound_and_keys(m):
    m = copy.deepcopy(m)
    m["end_to_end"][-1]["bound"] = 0.3
    m["end_to_end"][0]["why"] = "x"
    errs = manifest.validate(m)
    assert any("out of range" in e for e in errs)
    assert any("keys" in e for e in errs)


def test_new_cell_metric_and_mix_found_by_name(m, tmp_path, monkeypatch):
    """A later PR adds files and entries only: a traffic mix, a limits
    file and a metric reader, found by the names in BENCHMARK.json."""
    m = copy.deepcopy(m)
    traffic = tmp_path / "traffic"
    limits = tmp_path / "limits"
    metrics = tmp_path / "metrics"
    for d in (traffic, limits, metrics):
        d.mkdir()
    (traffic / "iid.json").write_text(json.dumps({"n_clients": 32}))
    (limits / "vgg16.iid.json").write_text(json.dumps(
        {"numbers": {"change_gap": {"limit": 0.1}}}))
    (metrics / "pad_share.train.py").write_text(
        "def read(r):\n    return 42.0\n")
    monkeypatch.setattr(loader, "TRAFFIC_DIR", str(traffic))
    monkeypatch.setattr(loader, "LIMITS_DIR", str(limits))
    monkeypatch.setattr(loader, "METRICS_DIR", str(metrics))
    m["workloads"].append({"name": "vgg16.iid", "config": "vgg16-cifar10",
                           "traffic": "iid", "chips": 1, "why": "control"})
    rps = next(e for e in m["end_to_end"] if e["name"] == "rounds_per_s")
    rps["workloads"].append("vgg16.iid")
    m["per_layer"].append({"name": "pad_share.train", "unit": "%",
                           "better": "lower", "source": "program_counter",
                           "layer": "window assembly (data/pipeline)",
                           "moves": "rounds_per_s",
                           "workloads": ["vgg16.iid"]})
    assert manifest.validate(m) == []
    assert loader.traffic(manifest.workload(m, "vgg16.iid")["traffic"]) == \
        {"n_clients": 32}
    assert loader.limits("vgg16.iid")["numbers"]["change_gap"]["limit"] == 0.1
    names = [x["name"] for x in manifest.per_layer_for(m, "vgg16.iid")]
    assert names == ["pad_share.train"]
    assert loader.metric_reader("pad_share.train").read(None) == 42.0
    assert "pad_share.train" not in [
        x["name"] for x in manifest.per_layer_for(m, "vgg16.noniid")]
