"""Everything a cell is made of, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names are:

    bench/configs/<config>.json       model, engine settings, deployment
    bench/references/<reference>.py   the plain reference a config names,
                                      with its architecture's work counts
    bench/adapters/<adapter>.py       the system under test a config names:
                                      how it is built, stepped and read
    bench/traffic/<traffic>.json      arrivals, mix, lengths, deadlines
    bench/arrivals/<arrival>.py       the arrival process a mix names
    bench/requests/<requests>.py      what a mix's arrivals become (a RAG
                                      pipeline, a session, ...)
    bench/metrics/<metric>.py         one reader per per-layer metric

A later change adds a cell, a mix, an arrival process, a kind of request, a
system or a metric by adding such files and ``BENCHMARK.json`` entries; no
file here changes for it. A mix may hold only keys that the generator, its
arrival process or its kind of request reads, so a key that nothing reads
(a burst setting under a process without bursts) is refused, never ignored.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


REFERENCE_NEEDS = ("Dims", "make_weights", "segment_layout", "forward_logits",
                   "step_flops", "kernel_work")


class SpecError(ValueError):
    """A cell, file or metric that BENCHMARK.json names cannot be found."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = field(default=ROOT)

    @property
    def adapter(self) -> ModuleType:
        return load_adapter(self.config["adapter"], self.root)

    @property
    def arrival(self) -> ModuleType:
        return load_arrival(self.traffic["arrival"], self.root)

    @property
    def requests(self) -> ModuleType:
        return load_requests(self.traffic["requests"], self.root)


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files loaded."""
    bench = bench if bench is not None else load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {w['config']!r}")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"no traffic file {traffic_path}")
    cell = Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config,
        traffic=json.loads(traffic_path.read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        root=root)
    check_traffic(cell.traffic, root)
    load_adapter(config["adapter"], root)
    return cell


def check_traffic(traffic: dict, root: Path = ROOT) -> None:
    """Refuse a mix that names an unknown arrival process or kind of
    request, or holds a key that none of the generator, its arrival process
    and its kind of request reads."""
    from bench.harness import traffic as tr

    for key in ("arrival", "requests"):
        if key not in traffic:
            raise SpecError(f"the mix names no {key!r}")
    known = set(tr.KEYS) | set(load_arrival(traffic["arrival"], root).KEYS) | set(
        load_requests(traffic["requests"], root).KEYS)
    unread = sorted(set(traffic) - known)
    if unread:
        raise SpecError(f"nothing reads the mix's keys {unread} (arrival "
                        f"{traffic['arrival']!r}, requests {traffic['requests']!r})")


def _load_module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    modname = f"{modname}_{hashlib.sha1(str(path).encode()).hexdigest()[:8]}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def load_reader(metric: str, root: Path = ROOT) -> ModuleType:
    """The reader of per-layer metric ``metric``: a module with ``read(ctx)``
    returning a number, or None where it finds nothing to read."""
    mod = _load_module(root / "bench" / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}")
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric reader {metric!r} has no read(ctx)")
    return mod


def _load_named(kind: str, name: str, root: Path, needs: tuple) -> ModuleType:
    mod = _load_module(root / "bench" / kind / f"{name}.py",
                       f"bench_{kind}_{name.replace('.', '_')}")
    missing = [a for a in needs if not hasattr(mod, a)]
    if missing:
        raise SpecError(f"bench/{kind}/{name}.py lacks {missing}")
    return mod


def load_arrival(name: str, root: Path = ROOT) -> ModuleType:
    """An arrival process: ``times(traffic, start, length, rng)`` gives the
    due times of one phase; ``KEYS`` names the mix keys it reads."""
    return _load_named("arrivals", name, root, ("times", "KEYS"))


def load_requests(name: str, root: Path = ROOT) -> ModuleType:
    """A kind of request: ``Source(engine, traffic, seed, vocab)`` turns
    arrivals into work for the system; ``KEYS`` names the mix keys it
    reads."""
    return _load_named("requests", name, root, ("Source", "KEYS"))


def load_adapter(name: str, root: Path = ROOT) -> ModuleType:
    """The system under test as the harness drives it (see
    ``bench/adapters/generation_engine.py`` for what one provides)."""
    return _load_named("adapters", name, root, (
        "import_program", "build", "warm", "busy", "step", "sync", "counters",
        "request_view", "prompt_segments", "trace_hooks", "plan_view"))


def load_reference(name: str, root: Path = ROOT) -> ModuleType:
    """The plain reference a configuration names (``"reference"`` key):
    ``Dims.from_config``, ``make_weights``, ``segment_layout`` and
    ``forward_logits`` for the weights and the check, and the
    architecture's work counts that per-layer metrics read as
    ``ctx["reference"]``: ``step_flops(dims, plan)`` (None for a step it
    cannot count) and ``kernel_work(kernel, dims, config, plan)`` (FLOPs
    and bytes, None where the step does not run the kernel)."""
    mod = _load_module(root / "bench" / "references" / f"{name}.py",
                       f"bench_reference_{name.replace('.', '_')}")
    missing = [a for a in REFERENCE_NEEDS if not hasattr(mod, a)]
    if missing:
        raise SpecError(f"bench/references/{name}.py lacks {missing}")
    return mod


def load_peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks. An unknown ``device_kind`` is an error,
    never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["chips"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table['chips'])})")
    return table["chips"][device_kind]
