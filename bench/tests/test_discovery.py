"""The harness is driven by data: a new traffic mix, arrival process, kind
of request, system adapter, configuration or per-layer metric is a new file
plus BENCHMARK.json entries, and no file that is already there changes."""
import json
import shutil

import pytest

from bench.harness import spec
from bench.harness import traffic as tr

from .conftest import ROOT

BURSTS = '''"""Test arrival process: all arrivals of a phase in bursts."""
import numpy as np

KEYS = ("rate_per_s", "burst_size")


def times(traffic, start, length, rng):
    n = max(int(round(traffic["rate_per_s"] * length)), 1)
    k = int(traffic["burst_size"])
    heads = np.sort(rng.random(-(-n // k))) * length
    return np.sort(np.repeat(heads, k)[:n]) + start
'''


@pytest.fixture
def copy_root(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def snapshot(root):
    return {p: p.read_bytes() for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def add_cell(root, name, traffic_name, config_name=None):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = bench["configs"][0]["name"] if config_name is None else config_name
    bench["workloads"].append({"name": name, "config": cfg, "traffic": traffic_name,
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def hot_mix(root):
    return json.loads((root / "bench" / "traffic" / "rag_hot.json").read_text())


def test_new_files_make_a_cell_and_a_metric(copy_root):
    before = snapshot(copy_root)
    mix = hot_mix(copy_root)
    mix["universe"] = 7
    (copy_root / "bench" / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (copy_root / "bench" / "metrics" / "new_metric.py").write_text(
        'LAYER = "cache (serving/paged_cache.py)"\nUNIT = "%"\nMOVES = "itl_p95_ms"\n\n'
        'def read(ctx):\n    return 42.0\n')
    bench = add_cell(copy_root, "x.new", "new_mix")
    bench["per_layer"].append({"name": "new_metric", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "cache (serving/paged_cache.py)",
                               "moves": "itl_p95_ms", "workloads": ["x.new"]})
    (copy_root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.find_cell("x.new", copy_root)
    assert cell.traffic["universe"] == 7
    assert cell.config["name"] == bench["configs"][0]["name"]
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert spec.load_reader("new_metric", copy_root).read({}) == 42.0
    after = snapshot(copy_root)
    assert {p: b for p, b in after.items() if p in before} == before


def test_a_new_arrival_process_is_a_new_file(copy_root):
    before = snapshot(copy_root)
    (copy_root / "bench" / "arrivals" / "bursts.py").write_text(BURSTS)
    mix = dict(hot_mix(copy_root), arrival="bursts", burst_size=4)
    (copy_root / "bench" / "traffic" / "bursty.json").write_text(json.dumps(mix))
    add_cell(copy_root, "x.bursty", "bursty")
    cell = spec.find_cell("x.bursty", copy_root)
    arr = tr.schedule(cell.traffic, 5, 40.0, cell.arrival)
    win = [a.due for a in arr if a.phase == "window"]
    assert len(win) == round(mix["rate_per_s"] * 40.0)
    # four arrivals share each due time: the new process, not the old one
    assert max(win.count(t) for t in win) == 4
    after = snapshot(copy_root)
    assert {p: b for p, b in after.items() if p in before} == before


def test_a_new_kind_of_request_and_a_new_adapter_are_new_files(copy_root):
    before = snapshot(copy_root)
    (copy_root / "bench" / "requests" / "sessions.py").write_text(
        'KEYS = ("turns",)\n\n\nclass Source:\n    pass\n')
    mix = {k: v for k, v in hot_mix(copy_root).items()
           if k in tr.KEYS or k == "rate_per_s"}
    mix.update(requests="sessions", turns=[2, 5])
    (copy_root / "bench" / "traffic" / "sessions.json").write_text(json.dumps(mix))
    src = copy_root / "bench" / "adapters" / "generation_engine.py"
    (copy_root / "bench" / "adapters" / "replica_group.py").write_text(src.read_text())
    cfg = json.loads((copy_root / "bench" / "configs" / "qwen2.5-3b.json").read_text())
    cfg.update(name="qwen-group", adapter="replica_group")
    (copy_root / "bench" / "configs" / "qwen-group.json").write_text(json.dumps(cfg))
    bench = json.loads((copy_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qwen-group",
                                 file="bench/configs/qwen-group.json"))
    (copy_root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_cell(copy_root, "x.sessions", "sessions", config_name="qwen-group")
    cell = spec.find_cell("x.sessions", copy_root)
    assert cell.requests.KEYS == ("turns",)
    assert cell.adapter.__file__.endswith("replica_group.py")
    after = snapshot(copy_root)
    assert {p: b for p, b in after.items() if p in before} == before


@pytest.mark.parametrize("change", [
    {"arrival": "mmpp"},                     # no such arrival process
    {"requests": "no_such_kind"},            # no such kind of request
    {"burst_factor": 4},                     # a key nothing reads
    {"doc_len_step": 128},                   # a key nothing reads any more
], ids=["unknown_arrival", "unknown_requests", "unread_key", "retired_key"])
def test_a_mix_nothing_can_run_as_written_is_refused(copy_root, change):
    mix = dict(hot_mix(copy_root), **change)
    (copy_root / "bench" / "traffic" / "odd.json").write_text(json.dumps(mix))
    add_cell(copy_root, "x.odd", "odd")
    with pytest.raises(spec.SpecError):
        spec.find_cell("x.odd", copy_root)


def test_unknown_names_are_refused(copy_root):
    with pytest.raises(spec.SpecError):
        spec.find_cell("no.such.cell", copy_root)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric", copy_root)
    with pytest.raises(spec.SpecError):
        spec.load_adapter("no_such_system", copy_root)


def test_every_entry_has_its_files_and_readers_agree():
    bench = spec.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        spec.load_reference(cell.config["reference"])
    for m in bench["per_layer"]:
        r = spec.load_reader(m["name"], ROOT)
        assert (r.LAYER, r.UNIT, r.MOVES) == (m["layer"], m["unit"], m["moves"])
