"""``BENCHMARK.json`` against the contract it is written to, every name
against its file, and the proof that the harness is driven by data: files
dropped into a copy are found with nothing edited."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head"
                   r"|expan|experts_per|n_embd|n_inner)")


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest()


def one_line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(mf.ROOT, p))
    assert 1 <= len(man["command"]) <= 32
    for word in man["command"]:
        assert one_line(word) and not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(mf.ROOT, word)):
            assert any(word.startswith(p + "/") for p in man["paths"]), word
    rs = man["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells has to fit: 2 + 14 x 24 runs of rs + 60 s,
    # 180 s a cell to compile, 1200 s spare, in 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs(man):
    assert 1 <= len(man["configs"]) <= 24
    names = [c["name"] for c in man["configs"]]
    files = [c["file"] for c in man["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        data = mf.load_config(man, c["name"])
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        # the plain reference beside it, found by the name the file gives
        family = mf.load_reference(data["reference"])
        for attr in ("model_config", "loss", "LOSS_TOL",
                     "train_flops_per_token", "flash_call_shape"):
            assert hasattr(family, attr), attr


def test_workloads(man):
    cells = man["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    configs = {c["name"] for c in man["configs"]}
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert one_line(c["why"]), len(c["why"])
        data = mf.load_workload(c["name"])
        for key in ("name", "config", "traffic", "chips"):
            assert data[key] == c[key], (c["name"], key)
        assert hasattr(mf.load_runner(data["runner"]), "run")
        assert data["batch"] % data["schedule"]["microbatches"] == 0
        assert data["batch"] % data["check_sequences"] == 0


def test_metrics(man):
    e2e, layer = man["end_to_end"], man["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {c["name"] for c in man["workloads"]}
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells and m.get("workloads", 1)
    by_name = {m["name"]: m for m in e2e}
    assert "setup_s" in by_name and by_name["setup_s"]["bound"] <= 0.1
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"])
        # the metric it should move is reported wherever this one is
        moved = by_name[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
        # its reader says the same as its entry
        reader = mf.load_metric(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES,
                reader.SOURCE) == (m["layer"], m["unit"], m["better"],
                                   m["moves"], m["source"]), m["name"]
        assert callable(reader.read)
    for cell in cells:
        mine = [m["name"] for m in mf.metrics_of(man, "end_to_end", cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert mf.metrics_of(man, "per_layer", cell)
    for name in ("kernels.flash_roofline_pct",):
        assert by_layer_unit(layer, name) == "%"


def by_layer_unit(layer, name):
    return next(m["unit"] for m in layer if m["name"] == name)


def test_files_under_paths_are_named_from_name_characters(man):
    for p in man["paths"]:
        for base, dirs, files in os.walk(os.path.join(mf.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), mf.ROOT)
                assert PATH.match(rel), rel


def test_names_are_checked_before_a_path_is_built():
    for bad in ("../x", "a/b", "", "a b", "x" * 65):
        with pytest.raises(ValueError):
            mf.load_workload(bad)


def test_new_files_are_found_without_editing_anything(tmp_path, man):
    """A later PR adds a configuration, a cell and a per-layer metric as
    files of their own plus entries in ``BENCHMARK.json``."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = dict(mf.load_workload(man["workloads"][0]["name"]),
                name="gpt2-tiny.train-b4s32", config="gpt2-tiny",
                traffic="train-b4s32", batch=4, seq=32)
    (copy / "benchmark/workloads/gpt2-tiny.train-b4s32.json").write_text(
        json.dumps(cell))
    config = dict(mf.load_config(man, "gpt2-medium"), name="gpt2-tiny")
    config["sizes"] = dict(config["sizes"], n_embd=64, n_layer=2, n_head=2)
    (copy / "benchmark/configs/gpt2-tiny.json").write_text(json.dumps(config))
    (copy / "benchmark/metrics/loop.steps.py").write_text(
        'LAYER = "train loop"\nUNIT = "count"\nBETTER = "higher"\n'
        'MOVES = "train.tokens_per_s"\nSOURCE = "program_counter"\n\n\n'
        'def read(run):\n    return float(len(run["spans"]["dispatch"]))\n')
    grown = json.loads(json.dumps(man))
    grown["configs"].append({"name": "gpt2-tiny", "source": "test",
                             "file": "benchmark/configs/gpt2-tiny.json",
                             "reduced": [], "why": "test"})
    grown["workloads"].append({"name": cell["name"], "config": "gpt2-tiny",
                               "traffic": "train-b4s32", "chips": 1,
                               "why": "test"})
    grown["per_layer"].append({"name": "loop.steps", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "train loop",
                               "moves": "train.tokens_per_s",
                               "workloads": [cell["name"]]})
    (copy / "BENCHMARK.json").write_text(json.dumps(grown))
    probe = (
        "import sys; sys.path.insert(0, '.')\n"
        "from benchmark.harness import manifest as mf\n"
        "man = mf.load_manifest()\n"
        "cell = 'gpt2-tiny.train-b4s32'\n"
        "assert mf.cell_entry(man, cell)['config'] == 'gpt2-tiny'\n"
        "assert mf.load_workload(cell)['seq'] == 32\n"
        "assert mf.load_config(man, 'gpt2-tiny')['sizes']['n_embd'] == 64\n"
        "names = [m['name'] for m in mf.metrics_of(man, 'per_layer', cell)]\n"
        "assert 'loop.steps' in names and 'pipe.stage_idle_max_pct' not in names\n"
        "old = [m['name'] for m in mf.metrics_of(man, 'per_layer', man['workloads'][0]['name'])]\n"
        "assert 'loop.steps' not in old\n"
        "run = {'spans': {'dispatch': [1, 2, 3]}}\n"
        "assert mf.load_metric('loop.steps').read(run) == 3.0\n"
        "print(mf.ROOT)\n")
    done = subprocess.run([sys.executable, "-c", probe], cwd=copy,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == str(copy)  # resolved inside the copy
