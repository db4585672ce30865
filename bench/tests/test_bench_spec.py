"""BENCHMARK.json and the files it names: every cell resolves to its
configuration, traffic mix, limits and metric readers; names, units and
entries keep to the format BENCHMARK.json follows; a new cell,
configuration, traffic mix or metric is found from new files alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import check, harness, reference
from bench.tests import tiny

ROOT = harness.ROOT
SPEC = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2 ** 31 + 7
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_entries_have_the_required_keys():
    assert set(SPEC) == KEYS["top"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert set(e) - {"workloads"} == KEYS[group], e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    names = [e["name"] for g in ("end_to_end", "per_layer")
             for e in SPEC[g]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert c.cfg["name"] == c.workload["config"]
    assert set(c.limits) == set(check.NUMBERS)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
    for key in c.workload["config"], c.workload["traffic"]:
        assert NAME.match(key)
    for k in next(x for x in SPEC["configs"]
                  if x["name"] == c.cfg["name"])["reduced"]:
        assert NAME.match(k) and k in c.cfg


def test_new_cell_needs_only_new_files(tmp_path):
    """A cell, configuration, traffic mix and per-layer metric added as
    files and entries resolve with no change to the harness."""
    root = tmp_path / "root"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    base = spec["configs"][0]
    cfg = harness.read_json(os.path.join(ROOT, base["file"]))
    cfg["name"] = "model-b"
    (root / "bench" / "configs" / "model-b.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "mix-b.json").write_text(json.dumps(
        dict(harness.read_json(os.path.join(
            ROOT, "bench", "traffic", spec["workloads"][0]["traffic"]
            + ".json")), clients=3)))
    (root / "bench" / "workloads" / "model-b.mix-b.json").write_text(
        json.dumps({"limits": {k: 1.0 for k in check.NUMBERS}}))
    (root / "bench" / "metrics" / "answer.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append(dict(base, name="model-b",
                                file="bench/configs/model-b.json"))
    spec["workloads"].append({"name": "model-b.mix-b", "config": "model-b",
                              "traffic": "mix-b", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "answer", "unit": "1",
                              "better": "higher", "source": "device_trace",
                              "layer": "device",
                              "moves": "train_tokens_per_s",
                              "workloads": ["model-b.mix-b"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.load_cell("model-b.mix-b", root=str(root))
    assert c.traffic["clients"] == 3 and c.cfg["name"] == "model-b"
    assert "answer" in [m["name"] for m in c.per_layer]
    other = harness.load_cell(spec["workloads"][0]["name"], root=str(root))
    assert "answer" not in [m["name"] for m in other.per_layer]


def test_new_architecture_needs_only_new_files(tmp_path):
    """A configuration whose ``arch`` module exists only in a temporary
    checkout (MiniCPM-2B with an untied head, a leaf the program's
    MiniCPM cells lack) resolves to that module; its tiny run passes
    the output check and its float8 control fails it."""
    root = tmp_path / "root"
    bench = root / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    module = bench / "models" / "minicpm_2b_untied.py"
    shutil.copy(os.path.join(harness.BENCH, "fixtures",
                             "minicpm_2b_untied.py"), module)
    spec = json.loads(json.dumps(SPEC))
    base = spec["workloads"][0]
    conf = next(c for c in spec["configs"] if c["name"] == base["config"])
    cfg = dict(harness.read_json(os.path.join(ROOT, conf["file"])),
               name="untied", arch="minicpm-2b-untied",
               tie_word_embeddings=False)
    (bench / "configs" / "untied.json").write_text(json.dumps(cfg))
    name = "untied." + base["traffic"]
    shutil.copy(bench / "workloads" / (base["name"] + ".json"),
                bench / "workloads" / (name + ".json"))
    spec["configs"].append(dict(conf, name="untied",
                                file="bench/configs/untied.json"))
    spec["workloads"].append(dict(base, name=name, config="untied"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    c = tiny.cell(name, root=str(root))
    assert os.path.samefile(c.model.__file__, module)
    wire = reference.wire_of(c.model, c.cfg)
    assert wire.names[-1] == "['lm_head']"
    assert c.model.program_config(c.cfg).tie_embeddings is False
    found, ok = tiny.run(c, SEED)
    assert ok, found
    pool = harness.make_pool(c, harness.keys(SEED)["data"])
    ctl = check.gaps(
        harness.reference_readings(c, SEED, pool, "float8_e4m3fn"),
        harness.reference_readings(c, SEED, pool))
    assert not check.verdict(ctl, c.limits), ctl


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
