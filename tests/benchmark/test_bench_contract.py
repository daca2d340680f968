"""BENCHMARK.json against the benchmark's contract, discovery of cells,
configurations, traffic and metrics by name (an added data-only cell
included), and the refusal to run anywhere but on a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = harness.load_json(ROOT, c["file"])
        assert set(c["reduced"]) <= set(conf["reduced"]) | set(conf)
    cells = [w["name"] for w in bench["workloads"]]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
    for name in names + cells + [m["name"] for m in metrics]:
        assert NAME.match(name), name
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_find_cell():
    cell = harness.find_cell("altair-mainnet-262k.boundary-block")
    assert cell.config["preset"] == "mainnet"
    assert {m["name"] for m in cell.end_to_end} == {"block_s", "setup_s"}
    assert "device_idle_share" in {m["name"] for m in cell.per_layer}
    epoch = "epoch_s" in {m["name"] for m in cell.per_layer}
    assert epoch == (cell.mix["block_slot_in_epoch"] == 0)


def test_electra_stays_as_data():
    """The electra configuration and the mid-epoch traffic have no cell
    (PERF.md section 7) but stay under `paths` for a later one."""
    with pytest.raises(KeyError):
        harness.find_cell("electra-mainnet-262k.mid-epoch-block")
    conf = harness.load_json(harness.HERE, "configs",
                             "electra-mainnet-262k.json")
    mix = harness.load_json(harness.HERE, "traffic", "mid-epoch-block.json")
    assert conf["fork"] == "electra" and conf["preset"] == "mainnet"
    assert mix["device_stage"] in harness.DEVICE_STAGES


def test_added_data_only_cell(tmp_path, bench):
    """A later PR adds a cell, its traffic and a metric as new files and
    entries: the harness finds them without an edit."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark")
    mix = harness.load_json(ROOT, "benchmark", "traffic",
                            "mid-epoch-block.json")
    mix["block_slot_in_epoch"] = 5
    (tmp_path / "benchmark" / "traffic" / "early-block.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark" / "metrics" / "blocks_read.py").write_text(
        "def read(ctx):\n    return float(ctx.blocks)\n")
    bench = dict(bench)
    bench["workloads"] = bench["workloads"] + [{
        "name": "altair-mainnet-262k.early-block",
        "config": "altair-mainnet-262k", "traffic": "early-block",
        "chips": 1, "why": "test"}]
    bench["per_layer"] = bench["per_layer"] + [{
        "name": "blocks_read", "unit": "blocks", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "block_s",
        "workloads": ["altair-mainnet-262k.early-block"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("altair-mainnet-262k.early-block",
                             root=str(tmp_path))
    assert cell.mix["block_slot_in_epoch"] == 5
    assert cell.config["fork"] == "altair"
    # a metric applies to the cells its `workloads` key lists
    assert [m["name"] for m in cell.per_layer] == ["blocks_read"]
    read = harness.load_reader("blocks_read", root=str(tmp_path))
    assert read(harness.Context(cell=cell, blocks=3, reduced=None,
                                device_kind="?")) == 3.0
    with pytest.raises(KeyError):
        harness.find_cell("no-such-cell", root=str(tmp_path))


def test_refuses_cpu(capsys):
    """No TPU: a non-zero exit naming the platform, and no result line."""
    assert harness.main(["--workload", "altair-mainnet-262k.boundary-block",
                         "--seed", "1", "--seconds", "1"]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    runs nothing and prints no result."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "altair-mainnet-262k.boundary-block", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
