"""The system under test: the program's ``GenerationEngine`` on one device.

The served path: the paged pool, compiled Pallas kernels on a TPU, the
ragged pipelined step, prefix sharing and the configured admission
scheduler. An adapter is the only kind of benchmark module that imports the
program (``src/repro``): it hands the program the benchmark's weights, and
reads back its outputs, timestamps and counters. What the harness asks of
an adapter:

    import_program(root)            import the program; ImportError if absent
    build(config, weights, dims)    the engine, from a configuration file's
                                    model and ``engine`` settings
    warm(engine)                    compile every program the window runs
    busy, step, sync, counters      the engine as the open loop drives it
    request_view, prompt_segments   a request and its prompt as plain data
    trace_hooks(engine, span, on_plan)  host spans around the engine's
                                    layers, and each dispatched step plan
    plan_view(plan)                 the work of one dispatched step
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np


def import_program(root: Path):
    """Put ``<root>/src`` on the path and import the program's packages.
    Raises ImportError where the checkout holds only the benchmark."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.apps  # noqa: F401
    import repro.serving.engine  # noqa: F401


def program_config(config: dict, dims):
    """The program's ``ModelConfig`` for a configuration file (published
    ``config.json`` keys, as run): a dense decoder, full attention."""
    from repro.configs.base import ATTN_FULL, ModelConfig

    return ModelConfig(
        name=config["name"], family="dense", num_layers=dims.layers,
        d_model=dims.d_model, d_ff=dims.d_ff, vocab_size=dims.vocab,
        num_heads=dims.heads, num_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        attn_type=ATTN_FULL, qkv_bias=dims.qkv_bias, rope_theta=dims.rope_theta,
        norm_eps=dims.eps, tie_embeddings=dims.tied, dtype=config["dtype"],
        source=config["source"])


def program_params(w: Dict, pcfg) -> Dict:
    """The benchmark's weights in the program's parameter tree (no copy:
    the same device arrays, regrouped). Refuses a tree whose structure or
    shapes differ from what the program's ``init_params`` builds."""
    import jax

    from repro.models import init_params

    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") if k in w}
    tree = {
        "embed": {"table": w["embed"]},
        "blocks": [{
            "norm1": {"scale": w["norm1"]}, "attn": attn,
            "norm2": {"scale": w["norm2"]},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }],
        "final_norm": {"scale": w["final_norm"]},
    }
    if "lm_head" in w:
        tree["lm_head"] = {"w": w["lm_head"]}
    want = jax.eval_shape(lambda: init_params(pcfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree changed: "
                         f"want {want}, got {got}")
    return tree


def build(config: dict, weights: Dict, dims):
    """The served engine with a configuration's engine settings."""
    from repro.serving.engine import GenerationEngine

    pcfg = program_config(config, dims)
    eng = config["engine"]
    engine = GenerationEngine(
        pcfg, params=program_params(weights, pcfg), max_batch=int(eng["max_batch"]),
        max_seq=int(eng["max_seq"]), n_blocks=int(eng["n_blocks"]),
        block_size=int(eng["block_size"]),
        prefill_chunk_size=int(eng["prefill_chunk_size"]),
        token_budget=int(eng["token_budget"]),
        pack_align=int(eng["pack_align"]), scheduler=eng["scheduler"],
        kernel=eng.get("kernel"))
    _count_evictions(engine.kv.pool)
    return engine


def _count_evictions(pool) -> None:
    """Count the warm blocks that allocation takes back (``evictions``),
    where the pool still allocates through ``_pop_block``."""
    pop = getattr(pool, "_pop_block", None)
    if pop is None:
        return
    pool.bench_evictions = 0

    def counted():
        if not pool.free_list and pool.cached:
            pool.bench_evictions += 1
        return pop()

    pool._pop_block = counted


def warm(engine) -> int:
    """Compile every step program the window can dispatch: the packed fused
    step at each aligned length (the program's own warm-up) and the decode
    step. Returns the number of programs warmed."""
    import jax

    n = engine.warmup_step_variants()
    fn, args = engine.step_program("decode")
    jax.block_until_ready(fn(*args))
    return n + 1


def busy(engine) -> bool:
    return bool(engine.waiting or any(engine.slots) or engine.pending)


def step(engine) -> None:
    engine.step()


def counters(engine) -> Dict[str, int]:
    """The program's own counters that per-layer metrics read, and the
    pool's occupancy: blocks held by live requests, warm blocks kept for
    reuse, free blocks, and warm blocks taken back so far."""
    s = engine.stats()
    out = {k: int(s.get(k, 0)) for k in (
        "steps", "tokens_out", "prefill_tokens", "prefix_hit_tokens",
        "host_hit_tokens", "preemptions", "fused_valid_tokens",
        "fused_slot_tokens")}
    pool = engine.kv.pool
    out["waiting"] = len(engine.waiting)
    out["active"] = sum(r is not None for r in engine.slots)
    out["blocks_free"] = len(pool.free_list)
    out["blocks_warm"] = len(pool.cached)
    out["blocks_held"] = pool.n_blocks - out["blocks_free"] - out["blocks_warm"]
    if hasattr(pool, "bench_evictions"):
        out["evictions"] = pool.bench_evictions
    return out


def sync(engine) -> None:
    """Wait until every step dispatched so far has run on the device."""
    import jax

    jax.block_until_ready((engine.kv.k, engine.kv.v))


def prompt_segments(prompt) -> List[tuple]:
    """(kind, tokens) per segment of a submitted prompt."""
    from repro.serving.segments import SegmentedPrompt

    if isinstance(prompt, SegmentedPrompt):
        return [("doc" if s.kind == "doc" else s.kind, np.asarray(s.tokens, np.int32))
                for s in prompt.segments]
    return [("tail", np.atleast_1d(np.asarray(prompt, np.int32)))]


def request_view(req) -> dict:
    """What the benchmark reads of a finished or running request."""
    return {"submitted_at": req.submitted_at, "first_token_at": req.first_token_at,
            "finished_at": req.finished_at, "token_gaps": list(req.token_gaps),
            "out_tokens": list(req.out_tokens), "done": bool(req.done),
            "truncated": bool(req.truncated), "max_new": int(req.max_new)}


def plan_view(plan) -> Optional[dict]:
    """The work of one dispatched step plan: which tokens it computed, with
    their cache slots and attention spans, and which rows sampled a token."""
    if plan is None:
        return None
    if plan.kind == "ragged":
        live = plan.row_of >= 0
        return {"kind": "ragged", "row_of": plan.row_of[live].copy(),
                "slots": plan.slots[live].copy(), "p_end": plan.p_end[live].copy(),
                "s_start": plan.s_start[live].copy(), "sampled": len(plan.emit_rows),
                "padded": int(plan.row_of.shape[0])}
    if plan.kind == "decode":
        rows = [row for _r, row, _f in plan.emit_rows]
        return {"kind": "decode", "ctx": (plan.starts[rows] + 1).copy(),
                "sampled": len(rows), "padded": int(plan.tokens.shape[0])}
    return {"kind": plan.kind, "sampled": len(plan.emit_rows)}


def trace_hooks(engine, span: Callable, on_plan: Callable) -> None:
    """For a traced run: host spans around the engine's calls into its
    layers (plan build, dispatch, materialize, copy drain), and
    ``on_plan(plan)`` for every dispatched step plan."""

    def wrap(obj, attr, name, after=None):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with span(name):
                out = fn(*a, **k)
            if after is not None:
                after(*a)
            return out

        setattr(obj, attr, wrapped)

    wrap(engine.control, "build_plan", "plan")
    wrap(engine.runner, "materialize", "materialize")
    wrap(engine, "_drain_copies", "copies")
    wrap(engine.runner, "dispatch", "dispatch", after=on_plan)
