"""Whole runs of the two-replica cell at toy size on four virtual CPU
devices, sound and with faults planted under the timed path; one JSON line
per case on standard output. ``test_engine_group.py`` starts it in a
process of its own, since the device count is fixed when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.group_cases sound state_unchanged ...
"""
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 2**31 + 11


def cell():
    from bench.harness import spec

    bench = spec.load_benchmark(ROOT)
    return spec.Cell(
        name="tiny.group", chips=4, config_name="tiny-group", traffic_name="tiny",
        config=json.loads((FIXTURES / "tiny_group_config.json").read_text()),
        traffic=json.loads((FIXTURES / "tiny_traffic.json").read_text()),
        end_to_end=list(bench["end_to_end"]), per_layer=[], root=ROOT)


def state_unchanged(group):
    """Every replica's step programs hand back the pools they were given."""
    for e in group.engines:
        for name in ("_ragged_step_jit", "_decode_dispatch_jit"):
            fn = getattr(e, name)

            def same_state(params, k, v, ks, vs, *rest, fn=fn):
                return (fn(params, k, v, ks, vs, *rest)[0], k, v, ks, vs)

            setattr(e, name, same_state)


def token_altered(group):
    """Each replica's sampled tokens are changed where they are produced."""
    for e in group.engines:
        sample, vocab = e.runner._sample_jit, e.cfg.vocab_size
        e.runner._sample_jit = lambda *a, s=sample, n=vocab: (s(*a) + 1) % n


def model_exchange_left_out(group):
    """The model axis's reduction left out: the attention output and MLP
    down projections keep only the first model shard's partial sums, as a
    device would that never adds the other's."""
    params = group.engines[0].params
    for blk in params["blocks"]:
        wo, wd = blk["attn"]["wo"], blk["mlp"]["w_down"]
        blk["attn"]["wo"] = wo.at[:, wo.shape[1] // 2:].set(0)
        blk["mlp"]["w_down"] = wd.at[:, wd.shape[1] // 2:].set(0)
    for e in group.engines:
        e.params = params


def replica_exchange_altered(group):
    """Blocks one replica takes from the shared host tier come back with
    their keys and values negated."""
    store = group.host_store
    read = store.read

    def negated(keys, owner=None):
        return tuple(-x if x is not None else None for x in read(keys, owner=owner))

    store.read = negated


FAULTS = {f.__name__: f for f in (state_unchanged, token_altered, model_exchange_left_out,
                                  replica_exchange_altered)}


def run(case: str) -> dict:
    from bench.harness import runner

    c = cell()
    adapter = c.adapter
    build = adapter.build
    if case != "sound":
        def faulty(config, weights, dims):
            g = build(config, weights, dims)
            FAULTS[case](g)
            return g
        adapter.build = faulty
    try:
        with tempfile.TemporaryDirectory() as work:
            r = runner.run_cell(c, SEED, 3.0, False, time.monotonic(), work,
                                log=lambda _m: None,
                                controls=("fp8",) if case == "sound" else ())
    finally:
        adapter.build = build
    return {"case": case, "correct": r["correct"], "checks": r["checks"],
            "counts": {k: r["counts"].get(k) for k in (
                "compiles_in_window", "cross_replica_host_hits_at_end",
                "cross_replica_host_hits_at_start", "control_fp8_gap_max",
                "pipelines", "pipelines_finished", "check_requests",
                "check_first_compared")}}


def main(cases):
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for case in cases:
        print(json.dumps(run(case)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
