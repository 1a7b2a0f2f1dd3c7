"""The benchmark's cells resolve to their files by name, and a cell that
is added as new files alone is found."""
import json
import shutil

import pytest

import bench

BENCH = bench.load_json(bench.CHECKOUT / "BENCHMARK.json")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = bench.resolve(BENCH, workload)
    w = cell.workload
    assert cell.config["name"] == w["config"]
    assert cell.traffic["entry"] == "run_cluster"
    assert (bench.HERE / "entries" / f"{cell.traffic['entry']}.py").is_file()
    assert set(cell.limits) >= {"dtheta_gap", "loss_gap"}
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "train_tokens_per_s"} <= names
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names


def test_files_and_names_follow_the_contract():
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        conf = bench.load_json(bench.CHECKOUT / c["file"])
        for key in c["reduced"]:
            assert key in conf["published"], key
            assert conf[key] != conf["published"][key], key
        assert conf["source"] == c["source"]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert (bench.HERE / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    for p in BENCH["paths"]:
        assert (bench.CHECKOUT / p).is_dir()


def test_peaks_are_keyed_by_device_kind():
    assert bench.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks_for("TPU v9 imaginary")


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    root = tmp_path / "chip"
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(bench.HERE / sub, root / sub)
    (root / "peaks.json").write_text((bench.HERE / "peaks.json").read_text())
    conf = bench.load_json(root / "configs" / "qwen2-1.5b-l4.json")
    conf["name"] = "newmodel-l2"
    (root / "configs" / "newmodel-l2.json").write_text(json.dumps(conf))
    tr = bench.load_json(root / "traffic" / "t512.json")
    tr["batch"] = 2
    (root / "traffic" / "t1024.json").write_text(json.dumps(tr))
    (root / "limits" / "newmodel-l2.t1024.json").write_text(
        json.dumps({"dtheta_gap": 0.1, "loss_gap": 0.1}))
    (root / "metrics" / "new_counter.py").write_text(
        "def read(ctx):\n    return ctx['grads'] * 2\n")
    spec = json.loads(json.dumps(BENCH))
    spec["configs"].append({"name": "newmodel-l2", "source": "x",
                            "file": "benchmarks/chip/configs/newmodel-l2.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "newmodel-l2.t1024",
                              "config": "newmodel-l2", "traffic": "t1024",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_counter", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "worker step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["newmodel-l2.t1024"]})
    cell = bench.resolve(spec, "newmodel-l2.t1024", root=root)
    assert cell.traffic["batch"] == 2 and cell.config["name"] == "newmodel-l2"
    assert "new_counter" in [m["name"] for m in cell.per_layer]
    got = bench.read_metrics([m for m in cell.per_layer
                              if m["name"] == "new_counter"],
                             {"grads": 21}, root=root)
    assert got == {"new_counter": {"value": 42.0, "unit": "1"}}
    old = bench.resolve(spec, BENCH["workloads"][0]["name"], root=root)
    assert "new_counter" not in [m["name"] for m in old.per_layer]


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        bench.resolve(BENCH, "no-such-cell")
