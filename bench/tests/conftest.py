"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
repository root. They run on the CPU at toy sizes and need no chip."""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny_cell():
    """A whole cell at toy size: a two-layer model and a light mix, with
    every end-to-end metric of BENCHMARK.json."""
    from bench.harness import spec

    bench = spec.load_benchmark(ROOT)
    return spec.Cell(
        name="tiny.mix", chips=1, config_name="tiny", traffic_name="tiny",
        config=json.loads((FIXTURES / "tiny_config.json").read_text()),
        traffic=json.loads((FIXTURES / "tiny_traffic.json").read_text()),
        end_to_end=list(bench["end_to_end"]), per_layer=[], root=ROOT)
