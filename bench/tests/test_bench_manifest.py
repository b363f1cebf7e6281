"""BENCHMARK.json against the benchmark's contract, and the data-driven
layout: a cell, a configuration, a mix and a metric added as files are
found by name with no edit."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as R  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_and_entry_keys(manifest):
    assert set(manifest) == KEYS
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert manifest["paths"] == ["bench"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_units_and_lengths(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    assert len(json.dumps(manifest)) <= 64 * 1024


def test_bounds(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_run_seconds_fits_a_check_of_24_cells(manifest):
    rs = manifest["run_seconds"]
    assert 1 <= rs <= 51 and isinstance(rs, int)
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_cells_resolve_to_files(manifest):
    pairs = set()
    four = 0
    for w in manifest["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        _, cell, conf, mix, cellfile = R.resolve(w["name"], root=ROOT)
        assert os.path.exists(os.path.join(BENCH, "families",
                                           conf["family"] + ".py"))
        assert cellfile["limits"]
    assert four <= max(1, len(manifest["workloads"]) // 2)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == c["reduced"]
        for k in ("source", "deployment", "assumed"):
            assert conf[k]


def test_every_metric_moves_a_metric_its_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for c in m.get("workloads", cells):
            assert R.applies(e2e[m["moves"]], cells[c], manifest), (m, c)
        assert hasattr(R.load_reader(m["name"]), "read")
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"], m["layer"])
    for name, cell in cells.items():
        rep = [m for m in manifest["end_to_end"]
               if R.applies(m, cell, manifest)]
        assert "setup_s" in [m["name"] for m in rep] and len(rep) >= 2
        assert any(R.applies(m, cell, manifest)
                   for m in manifest["per_layer"])


def test_a_new_cell_config_mix_and_metric_need_no_edit(tmp_path, manifest):
    """Copy the benchmark, add a throwaway configuration of a known family,
    a mix, a cell and a per-layer metric as new files plus manifest
    entries, and run the cell's CPU rehearsal from the copy."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    b = root / "bench"
    conf = json.loads((b / "configs" / "bert-base-s80.json").read_text())
    conf = dict(conf["rehearsal"], family="bert", dtype="bfloat16",
                serving=dict(conf["serving"], tile=[16, 16], sparsity=0.5),
                source="test", deployment="test", assumed={}, reduced=[])
    (b / "configs" / "tiny-bert.json").write_text(json.dumps(conf))
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(
        {"batch": 2, "seq": 16, "pool": 2, "check_samples": 1}))
    (b / "workloads" / "tiny-bert.tiny-mix.json").write_text(json.dumps(
        {"limits": {"logit_err": 0.05}}))
    (b / "metrics" / "tiny_requests.py").write_text(
        "def read(ctx):\n    return float(ctx['record']['attempted'])\n")
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny-bert", "source": "test",
                         "file": "bench/configs/tiny-bert.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny-bert.tiny-mix",
                           "config": "tiny-bert", "traffic": "tiny-mix",
                           "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "seq_per_s":
            e["workloads"].append("tiny-bert.tiny-mix")
    m["per_layer"].append({"name": "tiny_requests", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "client", "moves": "seq_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    _, cell, c, mix, cf = R.resolve("tiny-bert.tiny-mix", root=str(root))
    assert c["family"] == "bert" and mix["batch"] == 2
    reader = R.load_reader("tiny_requests", bench=str(b))
    assert reader.read({"record": {"attempted": 3}}) == 3.0
    assert R.applies(m["per_layer"][-1], cell, m)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload",
         "tiny-bert.tiny-mix", "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearsal"],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert "metrics" not in line


def test_refuses_to_run_without_a_chip():
    """Off the chip, a measured run exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "bert-base-s80.qa384-b1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
