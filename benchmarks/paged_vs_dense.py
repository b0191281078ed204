"""A/B: paged (block-table) engine vs dense per-slot engine.

Measures batched-decode throughput for both cache backends on the same
weights and the same workload, plus the paged-only wins: admission-controlled
memory (pool utilization) and prefix-block sharing across RAG requests that
embed the same retrieved context.

    PYTHONPATH=src python benchmarks/paged_vs_dense.py [--smoke]
"""
from __future__ import annotations

import time

import argparse

from _report import latency_row, parse_cli, print_latency_ms

import jax
import numpy as np

from repro.configs import get_arch, smoke_variant
from repro.models import init_params
from repro.serving.engine import GenerationEngine


def make_workload(n_requests: int, ctx_len: int, tail_len: int, max_new: int, seed: int = 0):
    """RAG-shaped prompts: a shared retrieved-context prefix + unique tail."""
    rng = np.random.default_rng(seed)
    ctx = rng.integers(0, 400, size=ctx_len)
    reqs = []
    for _ in range(n_requests):
        tail = rng.integers(0, 400, size=tail_len)
        reqs.append((np.concatenate([ctx, tail]), max_new))
    return reqs


def kv_block_bytes(cfg, block_size: int, kv_dtype: str = None) -> int:
    """HBM bytes one paged KV block occupies: K+V payload plus (for int8)
    the per-block, per-KV-head f32 scale-pool entries."""
    import jax.numpy as jnp

    G, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    if kv_dtype == "int8":
        return 2 * G * block_size * kvh * hd + 2 * G * kvh * 4
    return 2 * G * block_size * kvh * hd * jnp.dtype(kv_dtype or cfg.dtype).itemsize


def greedy_agreement(rows_a, rows_b) -> float:
    """Fraction of positions where two runs' greedy tokens agree (over the
    shorter of each request pair)."""
    match = total = 0
    for a, b in zip(rows_a, rows_b):
        n = min(len(a), len(b))
        match += sum(int(x == y) for x, y in zip(a[:n], b[:n]))
        total += n
    return match / max(total, 1)


def run_backend(backend: str, cfg, params, workload, max_batch: int,
                max_seq: int, kernel: str = "reference", kv_dtype: str = None):
    eng = GenerationEngine(
        cfg, params=params, max_batch=max_batch, max_seq=max_seq,
        backend=backend, kernel=kernel, kv_dtype=kv_dtype,
    )
    # warm up jit caches (prefill buckets / chunks + decode) off the clock
    eng.submit(workload[0][0], max_new=2)
    eng.run_until_done()
    reqs = [eng.submit(p, max_new=m) for p, m in workload]
    t0 = time.perf_counter()
    eng.run_until_done()
    wall = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    out_tokens = sum(len(r.out_tokens) for r in reqs)
    stats = eng.stats()
    return {
        "backend": eng.backend if kv_dtype is None else f"{eng.backend}-{kv_dtype}",
        "wall_s": wall,
        "out_tokens": out_tokens,
        "tok_per_s": out_tokens / wall,
        "decode_steps": stats["steps"],
        "prefill_tokens": stats["prefill_tokens"],
        "prefix_hit_tokens": stats.get("prefix_hit_tokens", 0),
        "preemptions": stats.get("preemptions", 0),
        "tokens": [list(r.out_tokens) for r in reqs],
        **latency_row(eng.latency_summary(),
                      ("ttft_p50", "ttft_p95", "tpot_p50", "tpot_p95")),
    }


def main(smoke: bool = False, kernel: str = "reference", kv_dtype: str = None):
    cfg = smoke_variant(get_arch("smollm-135m"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_batch, max_seq = 4, 256
    n_requests, max_new = (4, 8) if smoke else (12, 24)
    workload = make_workload(n_requests=n_requests, ctx_len=96, tail_len=8,
                             max_new=max_new)

    # the kernel flag only affects the paged hot path; dense stays reference
    rows = [run_backend(b, cfg, params, workload, max_batch, max_seq,
                        kernel=kernel if b == "paged" else "reference")
            for b in ("dense", "paged")]
    if kv_dtype is not None:
        rows.append(run_backend("paged", cfg, params, workload, max_batch,
                                max_seq, kernel=kernel, kv_dtype=kv_dtype))
    if kernel != "reference":
        print(f"[paged backend hot path: kernel={kernel}]")

    hdr = ("backend", "wall_s", "out_tok", "tok/s", "steps", "prefill_tok",
           "prefix_hits", "preempt")
    print(f"{hdr[0]:>8} {hdr[1]:>8} {hdr[2]:>8} {hdr[3]:>8} {hdr[4]:>6} "
          f"{hdr[5]:>12} {hdr[6]:>12} {hdr[7]:>8}")
    for r in rows:
        print(f"{r['backend']:>8} {r['wall_s']:>8.3f} {r['out_tokens']:>8d} "
              f"{r['tok_per_s']:>8.1f} {r['decode_steps']:>6d} "
              f"{r['prefill_tokens']:>12d} {r['prefix_hit_tokens']:>12d} "
              f"{r['preemptions']:>8d}")
    print_latency_ms(rows, "backend",
                     ("ttft_p50", "ttft_p95", "tpot_p50", "tpot_p95"))
    dense, paged = rows[0], rows[1]
    print(f"\npaged/dense throughput: {paged['tok_per_s'] / dense['tok_per_s']:.2f}x")
    saved = dense["prefill_tokens"] - paged["prefill_tokens"]
    print(f"prefill tokens saved by prefix sharing: {saved} "
          f"({paged['prefix_hit_tokens']} served from shared blocks)")

    if kv_dtype is not None:
        quant = rows[2]
        bs = 16  # GenerationEngine default block size
        fp16_blk = kv_block_bytes(cfg, bs, "float16")
        q_blk = kv_block_bytes(cfg, bs, kv_dtype)
        ratio = fp16_blk / q_blk
        agree = greedy_agreement(paged["tokens"], quant["tokens"])
        print(f"\n{kv_dtype} pool capacity: {ratio:.2f}x the blocks of fp16 "
              f"at equal HBM bytes ({q_blk}B vs {fp16_blk}B per block incl. "
              f"scale pools)")
        print(f"{kv_dtype} greedy-token agreement vs {paged['backend']}: "
              f"{agree:.1%}")
        assert ratio >= 1.9, (
            f"{kv_dtype} blocks-per-byte win {ratio:.2f}x below the 1.9x floor"
        )
        # one early flip cascades through the rest of a greedy sequence, and
        # random smoke weights leave tiny argmax gaps — pin a loose floor
        # here; the invariant suite pins the tight per-step threshold
        assert agree >= 0.75, (
            f"{kv_dtype} greedy agreement {agree:.1%} below the 75% floor"
        )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model / few requests: fast smoke run for CI")
    ap.add_argument("--kernel", default="reference",
                    choices=["reference", "pallas"],
                    help="paged-engine hot-path attention implementation")
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="also run the paged engine with quantized KV pools "
                         "and report capacity + greedy-agreement vs float")
    args = parse_cli(ap)
    main(smoke=args.smoke, kernel=args.kernel, kv_dtype=args.kv_dtype)
