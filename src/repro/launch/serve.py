"""Serving launcher: run a RAG application end-to-end under the Patchwork
runtime (simulated cluster, real control plane), or serve a real
model with batched requests via the generation engine.

    PYTHONPATH=src python -m repro.launch.serve --app crag --rate 32 --duration 30
    PYTHONPATH=src python -m repro.launch.serve --real --arch smollm-135m --smoke
    PYTHONPATH=src python -m repro.launch.serve --pipelines --smoke --rate 10 --duration 2

``--real``/``--pipelines`` serve the architecture at its published widths
unless ``--smoke`` asks for its tiny variant; on a TPU they run bfloat16 on
the compiled Pallas kernels (``python chip_smoke.py`` drives that path).
"""
from __future__ import annotations

import argparse
from typing import Dict, Optional

import numpy as np

from repro.apps import make_app
from repro.core.controller import MONOLITHIC, PATCHWORK, RAY_LIKE, PatchworkRuntime
from repro.data.workload import make_workload

ENGINES = {"patchwork": PATCHWORK, "monolithic": MONOLITHIC, "ray_like": RAY_LIKE}
DEFAULT_BUDGETS = {"GPU": 32, "CPU": 256, "RAM": 1024}


def serve_sim(app_name: str, rate: float, duration: float, engine: str = "patchwork",
              slo_s: float = 2.0, seed: int = 0, budgets=None):
    app = make_app(app_name)
    rt = PatchworkRuntime(app, budgets or DEFAULT_BUDGETS, engine=ENGINES[engine],
                          slo_s=slo_s, seed=seed)
    wl = make_workload(rate, duration, seed=seed)
    m = rt.run(wl)
    print(f"[serve:{engine}] app={app_name} rate={rate}/s: "
          f"thr={m.throughput:.1f}/s p50={m.latency_pct(50)*1e3:.0f}ms "
          f"p99={m.latency_pct(99)*1e3:.0f}ms slo_viol={m.slo_violation_rate*100:.1f}% "
          f"ctrl={np.mean(m.controller_overhead_s)*1e3:.3f}ms")
    return m


def serve_config(arch: str, smoke: bool = False):
    """The config a server runs: the architecture at its published widths
    (or its tiny ``smoke_variant``), in the platform's compute dtype —
    bfloat16 on a TPU, float32 elsewhere (CPU tests, Pallas interpreter)."""
    import jax

    from repro.configs import get_arch, smoke_variant

    cfg = get_arch(arch)
    if smoke:
        cfg = smoke_variant(cfg)
    return cfg.replace(
        dtype="bfloat16" if jax.default_backend() == "tpu" else "float32")


def build_engine(cfg, *, max_batch: int = 4, max_seq: int = 256,
                 n_blocks: Optional[int] = None, tp: int = 1, dp: int = 1,
                 params=None, seed: int = 0, **engine_kw):
    """One paged engine (or a ``DataParallelEngineGroup`` when ``dp > 1``)
    sized by batch, sequence length and pool blocks. ``tp``/``dp > 1`` put
    it on a ("data", "model") mesh — TP-resident weights, KV pools split by
    KV head (serving.sharded_pool). Params are initialized from ``seed``
    in one jitted program unless given. Returns the engine after checking
    that it fits the device (``pool_fit``)."""
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params
    from repro.serving.engine import DataParallelEngineGroup, GenerationEngine
    from repro.serving.sharded_pool import ShardedPoolLayout

    if params is None:
        params = jax.jit(init_params, static_argnums=0)(
            cfg, jax.random.PRNGKey(seed))
    layout = None
    if tp > 1 or dp > 1:
        layout = ShardedPoolLayout(make_serving_mesh(tp, dp), dp_blocks=dp > 1)
    if dp > 1:
        eng = DataParallelEngineGroup(
            cfg, dp=dp, max_batch=max_batch, max_seq=max_seq,
            n_blocks_per_replica=n_blocks, pool_layout=layout, seed=seed,
            params=params, **engine_kw)
    else:
        eng = GenerationEngine(cfg, params=params, max_batch=max_batch,
                               max_seq=max_seq, n_blocks=n_blocks,
                               pool_layout=layout, seed=seed, **engine_kw)
    pool_fit(eng)
    return eng


def pool_fit(eng) -> Dict[str, int]:
    """Bytes of the weights and of one copy of the KV pools on device 0.
    The step programs take the pools and return new ones without donating
    them, so a step holds two copies: refuse an engine whose weights plus
    two pool copies exceed the device's memory (where the backend reports
    it)."""
    import jax

    first = eng.engines[0] if hasattr(eng, "engines") else eng
    kv = first.kv
    dev = jax.devices()[0]

    def on_dev(arrays) -> int:
        return sum(int(s.data.nbytes) for a in arrays if a is not None
                   for s in a.addressable_shards if s.device == dev)

    weights = on_dev(jax.tree.leaves(first.params))
    pool = on_dev((kv.k, kv.v, kv.k_scale, kv.v_scale))
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit and weights + 2 * pool > limit:
        raise SystemExit(
            f"[serve] weights {weights / 2**30:.2f} GiB + 2 x pool "
            f"{pool / 2**30:.2f} GiB exceed the {limit / 2**30:.2f} GiB of "
            f"{dev.device_kind}: lower --n-blocks")
    return {"weights_bytes": weights, "pool_bytes": pool,
            "bytes_limit": limit}


def serve_requests(eng, prompts, max_new: int):
    """Submit every prompt (token array or ``SegmentedPrompt``), serve to
    completion, and print one line per request."""
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run_until_done()
    for r in reqs:
        ss = r.stream.stats if r.stream is not None else None
        chunks = f" chunks={ss.chunks_flushed}" if ss else ""
        print(f"  req {r.req_id}: {len(r.out_tokens)} tokens "
              f"ttft={1e3*(r.first_token_at - r.submitted_at):.0f}ms{chunks}")
    return reqs


def serve_real(arch: str, n_requests: int = 8, max_new: int = 12, *,
               smoke: bool = False, max_batch: int = 4, max_seq: int = 256,
               n_blocks: Optional[int] = None,
               tp: int = 1, dp: int = 1, preempt: str = "recompute",
               host_blocks: int = 0, pipeline: bool = True,
               kernel: Optional[str] = None, kv_dtype: str = None,
               audit: bool = False):
    """Serve a real model with batched requests on this host: the config
    at its published widths, or its tiny variant with ``smoke``.

    ``tp > 1`` shards the paged engine over a ("model",) mesh — TP-resident
    weights, KV pools partitioned by KV head (serving.sharded_pool); ``dp >
    1`` adds data-parallel replica engines with independent admission over
    block ranges of one shared pool. On CPU, force enough fake devices first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

    ``host_blocks > 0`` attaches the host-memory block tier (shared across
    DP replicas: cross-replica doc-block promotion); ``preempt="swap"``
    swaps preemption victims to that tier instead of recomputing them.

    ``kernel`` picks the hot-path attention: ``None`` lets the platform
    choose (compiled Pallas kernels on a TPU, the XLA gather reference
    elsewhere); a mesh needs ``"reference"``, since the Pallas kernels are
    single-device.

    ``kv_dtype="int8"`` stores the paged KV pools quantized (per-block
    absmax scales, dequant inside the kernels) — ~2x the block capacity at
    the same HBM budget and half the KV read bytes per decode step.
    Single-device only (the scale pools don't shard)."""
    if kernel == "pallas" and (tp > 1 or dp > 1):
        raise SystemExit("--kernel pallas is single-device: drop --tp/--dp")
    if kv_dtype and (tp > 1 or dp > 1):
        raise SystemExit("--kv-dtype int8 is single-device: drop --tp/--dp")
    cfg = serve_config(arch, smoke)
    eng = build_engine(cfg, max_batch=max_batch, max_seq=max_seq,
                       n_blocks=n_blocks, tp=tp, dp=dp, preempt=preempt,
                       host_blocks=host_blocks or None, pipeline=pipeline,
                       kernel=kernel, kv_dtype=kv_dtype)
    first = eng.engines[0] if dp > 1 else eng
    if audit:
        # contract audit before any traffic: collective census, callback
        # scan, int8 dtype flow, compile-cache sentinel (repro.analysis)
        from repro.analysis.jaxpr_audit import audit_engine

        report = audit_engine(first)
        for line in report.render().splitlines():
            print(f"[serve:audit] {line}")
        if not report.ok:
            raise SystemExit("[serve:audit] step-program contract violated")
    rng = np.random.default_rng(0)
    serve_requests(eng, [rng.integers(0, cfg.vocab_size, rng.integers(4, 32))
                         for _ in range(n_requests)], max_new)
    stats = eng.stats()
    mode = "pipelined" if pipeline else "sync"
    print(f"[serve:real] {cfg.name}: tp={tp} dp={dp} preempt={preempt} "
          f"mode={mode} kernel={first.kernel} "
          f"kv={stats.get('kv_dtype', kv_dtype or 'float')} "
          f"{stats['tokens_out']} tokens out")
    if "padded_token_fraction" in stats:
        print(f"[serve:real] fused-step padding: "
              f"{100 * stats['padded_token_fraction']:.1f}% of slot tokens")
    if "host_gap_s" in stats:
        print(f"[serve:real] host gap: {1e3 * stats['host_gap_s']:.1f}ms total "
              f"over {stats['dispatches']} dispatches "
              f"(copy ops drained: {stats.get('copy_ops_drained', 0)})")
    if "host_store" in stats:
        print(f"[serve:real] host tier: {stats['host_store']}")
    if tp > 1 and dp == 1:
        print(f"[serve:real] fused-step collectives: {eng.audit_collectives()}")
    return eng


def serve_pipelines(arch: str, rate: float, duration: float, *,
                    smoke: bool = False, max_batch: int = 4,
                    max_seq: int = 256, n_blocks: Optional[int] = None,
                    kernel: Optional[str] = None,
                    arrival: str = "poisson", session_fraction: float = 0.3,
                    host_blocks: int = 128, seed: int = 0,
                    wall_clock: bool = False, params=None, **engine_kw):
    """Adaptive RAG pipelines open-loop on the real engine: a seeded
    ``core.workload`` trace of mixed SLO classes (multi-turn sessions
    included) replays through ``apps.OpenLoopDriver`` with EDF-slack
    priorities; reports per-class violation rate and the session-KV reuse
    the host tier delivered. Sizing, ``smoke`` and ``kernel`` as in
    ``serve_real``; ``params`` reuses an already-initialized model and
    ``engine_kw`` passes further ``GenerationEngine`` options."""
    from repro.apps import OpenLoopDriver, VirtualClock, WallClock, make_app
    from repro.core.workload import DEFAULT_CLASSES, WorkloadSpec, generate

    cfg = serve_config(arch, smoke)
    eng = build_engine(cfg, max_batch=max_batch, max_seq=max_seq,
                       n_blocks=n_blocks, params=params, seed=seed,
                       kernel=kernel, prefill_chunk_size=32, token_budget=64,
                       scheduler="edf_slack", host_blocks=host_blocks,
                       **engine_kw)
    apps = {c.name: make_app(c.name, engine=eng) for c in DEFAULT_CLASSES}
    spec = WorkloadSpec(rate_rps=rate, duration_s=duration, arrival=arrival,
                        session_fraction=session_fraction, think_time_s=0.3)
    clock = WallClock() if wall_clock else VirtualClock(dt=0.02)
    drv = OpenLoopDriver(eng, apps, generate(spec, seed=seed), clock=clock,
                         seed=seed)
    drv.run()
    for name, s in sorted(drv.violation_summary().items()):
        print(f"[serve:pipelines] {name}: {int(s['completed'])} done "
              f"viol={100 * s['violation_rate']:.1f}% "
              f"mean_e2e={s['mean_latency_s']:.3f}s")
    st = eng.stats()
    ls = eng.latency_summary()
    print(f"[serve:pipelines] {cfg.name} kernel={eng.kernel}: "
          f"{st['tokens_out']} tokens out; session KV: "
          f"{st.get('session_shared_tokens', 0)} HBM-shared tokens, "
          f"{st.get('session_hit_tokens', 0)} host-promoted tokens "
          f"(session_hit_rate={ls.get('session_hit_rate', 0.0):.3f})")
    return drv


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="vrag",
                    choices=["vrag", "crag", "srag", "arag", "graphrag",
                             "planrag"])
    ap.add_argument("--engine", default="patchwork", choices=list(ENGINES))
    ap.add_argument("--rate", type=float, default=32.0)
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--slo", type=float, default=2.0)
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="with --real/--pipelines: serve the architecture's "
                         "tiny variant (2 layers, d_model 256) instead of "
                         "its published widths")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots of the engine")
    ap.add_argument("--max-seq", type=int, default=256,
                    help="longest prompt + generation a slot can hold")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="KV pool blocks (default: every slot can reach "
                         "--max-seq); weights + two pool copies must fit "
                         "the device")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for the paged engine "
                         "(shards KV pools by KV head over a 'model' mesh axis)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replica engines with independent "
                         "admission over block ranges of one shared pool")
    ap.add_argument("--preempt", default="recompute",
                    choices=["recompute", "swap", "cost"],
                    help="pool-exhaustion strategy: re-queue + re-prefill, "
                         "swap the victim's KV to the host tier, or pick "
                         "per victim from a swap-vs-recompute cost model")
    ap.add_argument("--kernel", default=None,
                    choices=["reference", "pallas"],
                    help="hot-path attention implementation: the XLA gather "
                         "reference, or the Pallas paged kernels (interpret "
                         "mode off-TPU; single-device only). Default: the "
                         "platform picks (Pallas on a TPU)")
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="paged KV pool storage format: int8 stores blocks "
                         "quantized with per-block absmax scales (2x block "
                         "capacity per HBM byte, kernels dequantize in "
                         "VMEM); default keeps the model dtype")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable double-buffered dispatch (sync oracle mode: "
                         "each step materializes before the next plan builds)")
    ap.add_argument("--host-blocks", type=int, default=0,
                    help="host-memory block-tier capacity (0 = no host tier "
                         "unless --preempt swap provisions one); shared "
                         "across --dp replicas for cross-replica doc reuse")
    ap.add_argument("--pipelines", action="store_true",
                    help="replay a seeded open-loop trace of mixed RAG "
                         "pipelines (sessions included) on the real engine "
                         "and report per-SLO-class violation rates")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "diurnal", "bursty"],
                    help="arrival process for --pipelines traces")
    ap.add_argument("--sessions", type=float, default=0.3,
                    help="fraction of --pipelines arrivals opening "
                         "multi-turn sessions")
    ap.add_argument("--wall-clock", action="store_true",
                    help="pace --pipelines arrivals in real time instead of "
                         "the deterministic virtual clock")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--audit", action="store_true",
                    help="with --real: run the repro.analysis step-program "
                         "contract audit (collectives, callbacks, int8 "
                         "flow, cache sentinel) at startup and abort on "
                         "any violation")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sizing = dict(smoke=args.smoke, max_batch=args.max_batch,
                  max_seq=args.max_seq, n_blocks=args.n_blocks,
                  kernel=args.kernel)
    if args.pipelines:
        serve_pipelines(args.arch, args.rate, args.duration,
                        arrival=args.arrival, session_fraction=args.sessions,
                        host_blocks=args.host_blocks or 128, seed=args.seed,
                        wall_clock=args.wall_clock, **sizing)
    elif args.real:
        serve_real(args.arch, tp=args.tp, dp=args.dp, preempt=args.preempt,
                   host_blocks=args.host_blocks, pipeline=not args.no_pipeline,
                   kv_dtype=args.kv_dtype, audit=args.audit, **sizing)
    else:
        serve_sim(args.app, args.rate, args.duration, args.engine, args.slo)


if __name__ == "__main__":
    main()
