"""Chip smoke test: serve qwen2.5-3b at its published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the (data=2, model=2) mesh path only

One chip: the model is built at its published widths (36 layers, d_model
2048, 16 query / 2 KV heads, head_dim 128, vocab 151936) in bfloat16 with
random weights from ``--seed``, and served through ``repro.launch.serve``
on the paged engine with the compiled Pallas kernels, the ragged pipelined
step and prefix sharing:

1. eight RAG-shaped requests (system segment, 2-4 documents from a shared
   set of six, a query), 32 new tokens each;
2. a few seconds of ``serve_pipelines`` open-loop RAG traffic;
3. the Pallas path against the XLA reference path on the same weights and
   prompts: last-prefill-token logits and one decode step.

``--four-chips`` serves the same model on a (data=2, model=2) mesh through
the reference kernels (the Pallas kernels are single-device) and compares
its last-prefill-token logits with a one-device engine on device 0.

Every phase that fails exits non-zero. Without a TPU the script exits
non-zero before any phase runs. The last line of a passing run is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "qwen2.5-3b"
# Pallas vs reference logits: max |a - b| over max |b|, per compared tensor.
# Both paths run the same bfloat16 weights and activations; they differ in
# where bfloat16 rounding happens inside attention (the kernel rounds
# probabilities taken against a running maximum and normalizes after the
# value product; the reference normalizes before rounding) and in summation
# order. bfloat16 keeps 8 significant bits, a relative rounding of 2^-9
# (0.2%) per operation; across 36 layers of residual updates, independent
# roundings of that size grow to a few percent of the largest logit. A
# wrong mask, block or scale moves logits by their own magnitude, so 5%
# still separates a fault from rounding. The same bound holds for the mesh
# comparison, where only the all-reduce order differs.
LOGIT_RTOL = 0.05
N_DOCS, N_REQUESTS, MAX_NEW = 6, 8, 32
SYSTEM_LEN, QUERY_LEN, DOC_LEN = 64, 32, (256, 512)
# packed fused-step lengths round up to this, bounding the step variants
# compiled at start-up (one 36-layer program per packed length)
PACK_ALIGN = 16


def fail(msg: str) -> int:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr)
    return 1


def rag_prompts(vocab: int, seed: int, n: int = N_REQUESTS):
    """``n`` RAG prompts: one system segment, 2-4 documents drawn from a
    shared set of ``N_DOCS`` (256-512 tokens each), a 32-token query."""
    import numpy as np

    from repro.serving.segments import assemble_prompt

    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, SYSTEM_LEN).astype(np.int32)
    docs = [rng.integers(0, vocab, int(rng.integers(*DOC_LEN, endpoint=True)))
            .astype(np.int32) for _ in range(N_DOCS)]
    out = []
    for _ in range(n):
        ids = rng.choice(N_DOCS, size=int(rng.integers(2, 5)), replace=False)
        query = rng.integers(0, vocab, QUERY_LEN).astype(np.int32)
        out.append(assemble_prompt(query, [docs[i] for i in ids],
                                   doc_ids=ids, system_tokens=system))
    return out


def prompt_logits(eng, params, prompts, decode_tokens=None):
    """Last-prefill-token logits of every prompt, computed in ONE packed
    call of the engine's fused step program on fresh pool blocks, then one
    decode step through the engine's decode program (feeding
    ``decode_tokens``, default each row's greedy token). Returns
    (prefill_logits, decode_logits, decode_tokens) as float32 numpy."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.segments import build_layout

    fused, _ = eng.step_program("fused_ragged")
    decode, _ = eng.step_program("decode")
    bs, mb = eng.block_size, eng.max_blocks
    tables = np.full((len(prompts), mb), -1, np.int32)
    cols = {k: [] for k in ("tokens", "row_of", "slots", "pos", "p_end", "s_start")}
    last, lens = [], []
    for b, p in enumerate(prompts):
        lay = build_layout(p, bs)
        n = lay.n_tokens
        blocks = eng.kv.pool.allocate(10_000 + b, n + 1)   # + the decode slot
        tables[b, :len(blocks)] = blocks
        cols["tokens"].append(lay.tokens)
        cols["row_of"].append(np.full(n, b))
        cols["slots"].append(np.arange(n))
        cols["pos"].append(lay.pos_ids)
        cols["p_end"].append(lay.attn_p_end)
        cols["s_start"].append(lay.attn_s_start)
        last.append(sum(lens) + n - 1)
        lens.append(n)
    flat = {k: jnp.asarray(np.concatenate(v).astype(np.int32)) for k, v in cols.items()}
    kv = eng.kv
    logits, k, v, ks, vs = fused(
        params, kv.k, kv.v, kv.k_scale, kv.v_scale, jnp.asarray(tables),
        flat["tokens"], flat["row_of"], flat["slots"], flat["pos"],
        flat["p_end"], flat["s_start"], jnp.asarray(np.asarray(last, np.int32)))
    logits = np.asarray(logits, np.float32)[:, :eng.cfg.vocab_size]
    if decode_tokens is None:
        decode_tokens = logits.argmax(-1).astype(np.int32)
    dec, *_ = decode(params, k, v, ks, vs, jnp.asarray(tables),
                     jnp.asarray(decode_tokens[:, None]),
                     jnp.asarray(np.asarray(lens, np.int32)))
    return logits, np.asarray(dec, np.float32)[:, :eng.cfg.vocab_size], decode_tokens


def rel_gap(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check_published(cfg) -> None:
    """The served config is the published one, in bfloat16."""
    want = dict(num_layers=36, d_model=2048, num_heads=16, num_kv_heads=2,
                head_dim=128, d_ff=11008, vocab_size=151936, dtype="bfloat16")
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise AssertionError(f"not the published {ARCH} widths: {got}")


def device_bytes(devices):
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices]


def one_chip(serve, jax, seed: int) -> None:
    import numpy as np

    dev = jax.devices()[0]
    cfg = serve.serve_config(ARCH)
    check_published(cfg)
    t0 = time.perf_counter()
    from repro.models import init_params

    params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    print(f"[chip_smoke] params: {cfg.param_count() / 1e9:.2f} B in {cfg.dtype}, "
          f"init {time.perf_counter() - t0:.1f} s")

    # phase 1: RAG requests through the served path
    prompts = rag_prompts(cfg.vocab_size, seed)
    max_seq = max(len(p) for p in prompts) + MAX_NEW + 16
    t0 = time.perf_counter()
    eng = serve.build_engine(cfg, max_batch=N_REQUESTS, max_seq=max_seq,
                             params=params, pack_align=PACK_ALIGN)
    fit = serve.pool_fit(eng)
    if eng.backend != "paged" or eng.kernel != "pallas":
        raise AssertionError(f"backend={eng.backend} kernel={eng.kernel}: "
                             "want the paged engine on the Pallas kernels")
    if not (eng.pipeline and eng.kv.prefix_sharing):
        raise AssertionError("want the pipelined step with prefix sharing")
    n_variants = eng.warmup_step_variants()
    print(f"[chip_smoke] engine: backend={eng.backend} kernel={eng.kernel} "
          f"pipeline={eng.pipeline} "
          f"n_blocks={eng.kv.pool.n_blocks} max_seq={max_seq} "
          f"pool={fit['pool_bytes'] / 2**30:.3f} GiB "
          f"weights={fit['weights_bytes'] / 2**30:.3f} GiB")
    print(f"[chip_smoke] compile: {n_variants} fused-step variants + engine "
          f"build in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    reqs = serve.serve_requests(eng, prompts, MAX_NEW)
    st = eng.stats()
    print(f"[chip_smoke] rag: {len(reqs)} requests, prompt tokens "
          f"{sum(len(p) for p in prompts)}, tokens out {st['tokens_out']}, "
          f"prefix_hit_tokens {st['prefix_hit_tokens']}, "
          f"wall {time.perf_counter() - t0:.1f} s (first decode compiles included)")
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        if len(toks) != MAX_NEW or toks.min() < 0 or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"request {r.req_id}: bad output {toks}")
    if st["tokens_out"] != N_REQUESTS * MAX_NEW or st["prefix_hit_tokens"] <= 0:
        raise AssertionError(f"tokens_out={st['tokens_out']} "
                             f"prefix_hit_tokens={st['prefix_hit_tokens']}")
    del eng, reqs
    gc.collect()

    # phase 2: open-loop RAG pipelines on the same config
    t0 = time.perf_counter()
    drv = serve.serve_pipelines(ARCH, rate=4.0, duration=3.0, max_batch=N_REQUESTS,
                                max_seq=1024, params=params, seed=seed,
                                pack_align=PACK_ALIGN)
    done = sum(int(s["completed"]) for s in drv.violation_summary().values())
    print(f"[chip_smoke] pipelines: {done} pipelines completed, kernel="
          f"{drv.engine.kernel}, wall {time.perf_counter() - t0:.1f} s")
    if done <= 0 or drv.engine.kernel != "pallas":
        raise AssertionError("pipelines phase completed nothing on the Pallas path")
    del drv
    gc.collect()

    # phase 3: Pallas vs reference on the same params and prompts
    cmp_prompts = prompts[:2]
    cmp_seq = max(len(p) for p in cmp_prompts) + 16
    got = serve.build_engine(cfg, max_batch=2, max_seq=cmp_seq, params=params)
    ref = serve.build_engine(cfg, max_batch=2, max_seq=cmp_seq, params=params,
                             kernel="reference")
    r_pre, r_dec, toks = prompt_logits(ref, params, cmp_prompts)
    g_pre, g_dec, _ = prompt_logits(got, params, cmp_prompts, toks)
    gaps = {"prefill": rel_gap(g_pre, r_pre), "decode": rel_gap(g_dec, r_dec)}
    agree = float(np.mean(g_pre.argmax(-1) == r_pre.argmax(-1)))
    print(f"[chip_smoke] pallas vs reference ({got.kernel} vs {ref.kernel}): "
          f"rel gap prefill {gaps['prefill']:.3e}, decode {gaps['decode']:.3e} "
          f"(tolerance {LOGIT_RTOL}); greedy agreement {agree:.2f}, not gated")
    for name, arr in (("pallas prefill", g_pre), ("pallas decode", g_dec)):
        if not np.all(np.isfinite(arr)):
            raise AssertionError(f"{name} logits not finite")
    if max(gaps.values()) > LOGIT_RTOL:
        raise AssertionError(f"logit gap {gaps} over {LOGIT_RTOL}")
    ms = dev.memory_stats() or {}
    print(f"[chip_smoke] peak_bytes_in_use {ms.get('peak_bytes_in_use')} "
          f"of bytes_limit {ms.get('bytes_limit')}")


def four_chips(serve, jax, seed: int) -> None:
    import numpy as np

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, have {len(devs)}")
    cfg = serve.serve_config(ARCH)
    check_published(cfg)
    from repro.models import init_params

    params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
    prompts = rag_prompts(cfg.vocab_size, seed)
    max_seq = max(len(p) for p in prompts) + MAX_NEW + 16
    t0 = time.perf_counter()
    # Pallas kernels are single-device: the mesh serves on the reference
    # kernels, chosen here explicitly
    mesh_eng = serve.build_engine(cfg, tp=2, dp=2, max_batch=4, max_seq=max_seq,
                                  params=params, kernel="reference",
                                  pack_align=PACK_ALIGN)
    first = mesh_eng.engines[0]
    print(f"[chip_smoke] mesh: data=2 model=2, kernel={first.kernel}, "
          f"{len(mesh_eng.engines)} replicas, build {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    reqs = serve.serve_requests(mesh_eng, prompts, MAX_NEW)
    st = mesh_eng.stats()
    print(f"[chip_smoke] mesh rag: {len(reqs)} requests, tokens out "
          f"{st['tokens_out']}, wall {time.perf_counter() - t0:.1f} s")
    if st["tokens_out"] != N_REQUESTS * MAX_NEW:
        raise AssertionError(f"mesh tokens_out={st['tokens_out']}")

    cmp_prompts = prompts[:2]
    m_pre, _, toks = prompt_logits(first, first.params, cmp_prompts)
    per_dev = device_bytes(devs)
    print("[chip_smoke] bytes_in_use per device while the mesh serves: "
          + " ".join(f"{d.id}:{b}" for d, b in zip(devs, per_dev)))
    # free the TP-placed copy of the weights before the one-device engine
    del mesh_eng, first, reqs
    gc.collect()
    one = serve.build_engine(cfg, max_batch=2, max_seq=max_seq, params=params,
                             kernel="reference")
    r_pre, _, _ = prompt_logits(one, params, cmp_prompts, toks)
    gap = rel_gap(m_pre, r_pre)
    agree = float(np.mean(m_pre.argmax(-1) == r_pre.argmax(-1)))
    print(f"[chip_smoke] mesh (tp=2 x dp=2) vs one device ({one.kernel}): rel gap "
          f"prefill {gap:.3e} (tolerance {LOGIT_RTOL}); greedy agreement "
          f"{agree:.2f}, not gated")
    if not np.all(np.isfinite(m_pre)) or gap > LOGIT_RTOL:
        raise AssertionError(f"mesh logit gap {gap} over {LOGIT_RTOL}")
    if min(per_dev[:4]) <= 0:
        raise AssertionError(f"a mesh device holds nothing: {per_dev}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) mesh phase and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the repro package is not beside this script ({e})")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (platform {dev.platform!r})")
    cache = enable_compile_cache()
    print(f"[chip_smoke] jax {jax.__version__}, device {dev.device_kind} x "
          f"{len(jax.devices())}, compile cache {cache}")
    try:
        if args.four_chips:
            four_chips(serve, jax, args.seed)
        else:
            one_chip(serve, jax, args.seed)
    except Exception as e:  # any failed phase fails the run
        import traceback

        traceback.print_exc()
        return fail(f"{type(e).__name__}: {e}")
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
