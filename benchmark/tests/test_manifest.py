"""``BENCHMARK.json`` against its contract, and the files it names."""
import hashlib
import json
import os
import shutil

import pytest

from benchmark import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = manifest.load(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_and_entry_keys():
    assert set(BENCH) == TOP_KEYS
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_names_units_and_text():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names + [w["config"] for w in BENCH["workloads"]] \
            + [w["traffic"] for w in BENCH["workloads"]] \
            + [k for c in BENCH["configs"] for k in c["reduced"]]:
        assert manifest.NAME_RE.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in [e["why"] for e in BENCH["configs"] + BENCH["workloads"]] \
            + [c["source"] for c in BENCH["configs"]] \
            + [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_paths_and_command():
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"][1].startswith("benchmark/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_run_seconds_fit_a_full_check():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert 1 <= BENCH["run_seconds"] <= 51 and total <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    c = manifest.cell(BENCH, ROOT, cell)
    assert c.traffic["kind"] in ("serve", "graph")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        mod = manifest.metric_module(ROOT, m["name"])
        assert mod.UNIT == m["unit"]
        assert callable(mod.read)
    for m in c.per_layer:
        mod = manifest.metric_module(ROOT, m["name"])
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])


def test_configs_are_files_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert sorted(doc["reduced"]) == sorted(c["reduced"])


def test_moves_names_an_end_to_end_metric_of_each_cell():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert manifest._applies(e2e[m["moves"]], cell), (m["name"], cell)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], m["layer"])
    assert all("\n" not in k for k in layers)


def test_four_chip_cells_are_few():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """An added traffic file, metric module and manifest entry make a new
    cell, and no file that was there changes."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = _digest(tmp_path / "benchmark")
    short = dict(json.load(open(manifest.traffic_path(ROOT, "chat"))),
                 prompt={"dist": "uniform", "min": 8, "max": 32})
    (tmp_path / "benchmark" / "traffic" / "short.json").write_text(
        json.dumps(short))
    (tmp_path / "benchmark" / "metrics" / "ttft_p50_s.py").write_text(
        "NAME, UNIT = 'ttft_p50_s', 's'\n"
        "LAYER, MOVES = 'admission', 'ttft_p90_s'\n"
        "def read(run):\n    return 1.0\n")
    bench["workloads"].append({"name": "yi-9b.short", "config": "yi-9b",
                               "traffic": "short", "chips": 1,
                               "why": "short prompts"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_s", "itl_p95_ms"):
            m["workloads"] = m["workloads"] + ["yi-9b.short"]
    bench["per_layer"].append({"name": "ttft_p50_s", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "admission", "moves": "ttft_p90_s",
                               "workloads": ["yi-9b.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = manifest.cell(bench, str(tmp_path), "yi-9b.short")
    assert c.traffic["prompt"]["max"] == 32
    assert c.config["model"] == "yi-9b"
    assert [m["name"] for m in c.per_layer] == ["ttft_p50_s"]
    assert manifest.metric_module(str(tmp_path), "ttft_p50_s").read(None) == 1
    after = _digest(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
