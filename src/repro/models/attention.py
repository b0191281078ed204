"""Attention variants: GQA full/SWA/chunked-local prefill + decode, and MLA.

Prefill uses a blockwise (flash-style) formulation: a ``lax.scan`` over query
blocks with the relevant KV span sliced per block, so the materialized score
tensor is O(S * span) instead of O(S^2). This is the XLA path used by models
and the oracle the Pallas kernels are checked against; it lowers on CPU and
TPU alike. Softmax statistics are kept in f32.

Decode attends one query token against the KV cache directly (the score
tensor is O(S), which is exactly the HBM-bandwidth-bound read the roofline
models).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN_CHUNKED_LOCAL,
    ATTN_FULL,
    ATTN_SWA,
)
from repro.models.layers import apply_rope, dense_init, rms_norm, zeros_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA parameter init
# ---------------------------------------------------------------------------


def init_attention(key, d_model, num_heads, num_kv_heads, head_dim, qkv_bias, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = zeros_init((num_heads * head_dim,), dtype)
        p["bk"] = zeros_init((num_kv_heads * head_dim,), dtype)
        p["bv"] = zeros_init((num_kv_heads * head_dim,), dtype)
    return p


def qkv_project(params, x, num_heads, num_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, num_heads, head_dim)
    k = k.reshape(B, S, num_kv_heads, head_dim)
    v = v.reshape(B, S, num_kv_heads, head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# blockwise (flash-style) prefill attention
# ---------------------------------------------------------------------------


def _grouped_scores(q_blk, k_span, scale):
    """q_blk: (B, bq, H, hd); k_span: (B, span, KVH, hd) -> (B, KVH, G, bq, span)."""
    B, bq, H, hd = q_blk.shape
    KVH = k_span.shape[2]
    G = H // KVH
    qg = q_blk.reshape(B, bq, KVH, G, hd)
    scores = jnp.einsum(
        "bqkgh,bskh->bkgqs", qg, k_span, preferred_element_type=jnp.float32
    )
    return scores * scale


def _grouped_out(probs, v_span, out_dtype):
    """probs: (B, KVH, G, bq, span); v_span: (B, span, KVH, hd) -> (B, bq, H, hd)."""
    B, KVH, G, bq, _ = probs.shape
    hd = v_span.shape[-1]
    out = jnp.einsum(
        "bkgqs,bskh->bqkgh", probs.astype(v_span.dtype), v_span,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, bq, KVH * G, hd).astype(out_dtype)


def _resolve_spec(S, S_kv, attn_type, window, chunk, causal, block_q, scale, hd):
    """Static blocking plan shared by forward and backward. S is the query
    length; S_kv the key/value length (cross-attention: S_kv != S)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    if S % block_q:
        # non-divisible S (e.g. hymba's +128 meta tokens): largest divisor
        # <= block_q, never one S-wide block (that would materialize the full
        # score matrix)
        block_q = math.gcd(S, block_q)
        if block_q < 16:
            block_q = S
    if attn_type == ATTN_SWA and window:
        span = min(window + block_q, S_kv)
    elif attn_type == ATTN_CHUNKED_LOCAL and chunk:
        span = min(chunk, S_kv)
    else:
        span = S_kv
    # backward pass 2 blocks over KV; fall back to one block if non-divisible
    block_kv = block_q if S_kv % block_q == 0 else S_kv
    return dict(
        S=S, S_kv=S_kv, attn_type=attn_type, window=window, chunk=chunk,
        causal=causal, block_q=block_q, span=span, nq=S // block_q,
        block_kv=block_kv, nkv=S_kv // block_kv, scale=scale,
    )


def _span_start(spec, i):
    S_kv, bq, span = spec["S_kv"], spec["block_q"], spec["span"]
    if spec["attn_type"] == ATTN_SWA and spec["window"] and span < S_kv:
        return jnp.maximum(0, (i + 1) * bq - span)
    if spec["attn_type"] == ATTN_CHUNKED_LOCAL and spec["chunk"] and span < S_kv:
        return (i * bq) // spec["chunk"] * spec["chunk"]
    # full attention: the span is the whole sequence. Return a CONSTANT zero —
    # a traced start would make the slice (and its transpose, a scatter)
    # dynamic, which forces the SPMD partitioner to all-gather the batch dim.
    return 0


def _block_mask(spec, qpos, kpos):
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), dtype=bool)
    if spec["causal"]:
        mask &= qpos[:, None] >= kpos[None, :]
    if spec["attn_type"] == ATTN_SWA and spec["window"]:
        mask &= kpos[None, :] > qpos[:, None] - spec["window"]
    if spec["attn_type"] == ATTN_CHUNKED_LOCAL and spec["chunk"]:
        mask &= (kpos[None, :] // spec["chunk"]) == (qpos[:, None] // spec["chunk"])
    return mask


def _constrain_scores(scores):
    """Shard the (B,KVH,G,bq,span) score/prob block. Prefer sharding KV heads
    over the model axis; when the head count doesn't divide it (most assigned
    archs at TP=16), shard the span (KV-length) dim instead — softmax and the
    PV product then reduce over a model-sharded dim, which GSPMD lowers to
    all-reduces (context-parallel attention, the TPU-native fallback)."""
    from repro.models.sharding import constrain, model_axis_size

    KVH, span = scores.shape[1], scores.shape[4]
    m = model_axis_size()
    if m > 1 and KVH % m == 0:
        return constrain(scores, "batch", "model", None, None, None)
    return constrain(scores, "batch", None, None, None, "model")


def _mask_bias(spec, qpos, kpos):
    """Additive f32 bias of shape (bq, span): 0 where visible, -inf where
    masked. Kept 2-D so no batch/head-broadcast boolean ever materializes."""
    mask = _block_mask(spec, qpos, kpos)
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)


def _fwd_block(spec, q, k, v, i):
    """One query block: returns (out_blk (B,bq,H,hd), lse_blk (B,KVH,G,bq))."""
    from repro.models.sharding import constrain

    bq, span = spec["block_q"], spec["span"]
    q_blk = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
    start = _span_start(spec, i)
    k_span = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
    v_span = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
    scores = _grouped_scores(q_blk, k_span, spec["scale"])  # f32 (B,KVH,G,bq,span)
    scores = scores + _mask_bias(spec, i * bq + jnp.arange(bq), start + jnp.arange(span))
    scores = _constrain_scores(scores)
    lse = jax.nn.logsumexp(scores, axis=-1)  # (B,KVH,G,bq)
    probs = jnp.exp(scores - lse[..., None])
    return _grouped_out(probs, v_span, q.dtype), lse


def _flash_forward(spec, q, k, v):
    B, S, H, _ = q.shape
    hd_v = v.shape[-1]  # MLA: value head dim != query head dim
    if spec["nq"] == 1:
        out, lse = _fwd_block(spec, q, k, v, 0)
        return out, lse[:, :, :, None, :]  # (B,KVH,G,1,bq)

    def body(_, i):
        return None, _fwd_block(spec, q, k, v, i)

    _, (blocks, lses) = jax.lax.scan(body, None, jnp.arange(spec["nq"]))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd_v)
    return out, lses.transpose(1, 2, 3, 0, 4)  # (B,KVH,G,nq,bq)


def _q_span_for_kv(spec, j):
    """Which (block-aligned) query span can see KV block j."""
    S, bq = spec["S"], spec["block_kv"]
    if spec["attn_type"] == ATTN_SWA and spec["window"]:
        span_q = min(bq + spec["window"], S)
    elif spec["attn_type"] == ATTN_CHUNKED_LOCAL and spec["chunk"]:
        return (j * bq) // spec["chunk"] * spec["chunk"], min(spec["chunk"], S)
    else:
        return 0, S  # full: all q (masked); constant start (see above)
    start = jnp.minimum(j * bq, S - span_q)
    return start, span_q


def _flash_backward(spec, res, dout):
    """Two-pass recompute backward (flash-attention style, scatter-free):
    pass 1 scans query blocks emitting dq; pass 2 scans KV blocks emitting
    dk/dv. No dynamic-update-slice accumulators, so GSPMD keeps every buffer
    batch-sharded. Only O, LSE and the inputs are saved from forward."""
    from repro.models.sharding import constrain

    q, k, v, out, lse = res
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    bq, span, nq, scale = spec["block_q"], spec["span"], spec["nq"], spec["scale"]

    hd_v = v.shape[-1]
    bkv, nkv = spec["block_kv"], spec["nkv"]
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # (B,S,H)
    lse_flat = lse.reshape(B, KVH, G, S)  # (B,KVH,G,nq,bq) -> per-position

    def recompute_probs(q_blk, k_span, qpos, kpos, lse_blk):
        scores = _grouped_scores(q_blk, k_span, scale)
        scores = scores + _mask_bias(spec, qpos, kpos)
        scores = _constrain_scores(scores)
        return jnp.exp(scores - lse_blk[..., None])  # (B,KVH,G,bq,span)

    def dq_block(_, i):
        q_blk = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        do_blk = jax.lax.dynamic_slice_in_dim(dout, i * bq, bq, axis=1)
        d_blk = jax.lax.dynamic_slice_in_dim(delta, i * bq, bq, axis=1)
        lse_blk = jax.lax.dynamic_slice_in_dim(lse_flat, i * bq, bq, axis=3)
        start = _span_start(spec, i)
        k_span = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_span = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        probs = recompute_probs(
            q_blk, k_span, i * bq + jnp.arange(bq), start + jnp.arange(span), lse_blk
        )
        do_g = do_blk.reshape(B, bq, KVH, G, hd_v).astype(jnp.float32)
        dp = jnp.einsum("bqkgh,bskh->bkgqs", do_g, v_span.astype(jnp.float32))
        d_g = d_blk.reshape(B, bq, KVH, G).transpose(0, 2, 3, 1)
        ds = probs * (dp - d_g[..., None]) * scale
        dq_blk = jnp.einsum("bkgqs,bskh->bqkgh", ds, k_span.astype(jnp.float32))
        dq_blk = constrain(
            dq_blk.reshape(B, bq, H, hd).astype(q.dtype), "batch", None, "model", None
        )
        return None, dq_blk

    def dkv_block(_, j):
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * bkv, bkv, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * bkv, bkv, axis=1)
        start_q, span_q = _q_span_for_kv(spec, j)
        q_span = jax.lax.dynamic_slice_in_dim(q, start_q, span_q, axis=1)
        do_span = jax.lax.dynamic_slice_in_dim(dout, start_q, span_q, axis=1)
        d_span = jax.lax.dynamic_slice_in_dim(delta, start_q, span_q, axis=1)
        lse_span = jax.lax.dynamic_slice_in_dim(lse_flat, start_q, span_q, axis=3)
        probs = recompute_probs(
            q_span, k_blk, start_q + jnp.arange(span_q), j * bkv + jnp.arange(bkv), lse_span
        )  # (B,KVH,G,span_q,bkv)
        do_g = do_span.reshape(B, span_q, KVH, G, hd_v).astype(jnp.float32)
        dv_blk = jnp.einsum("bkgqs,bqkgh->bskh", probs, do_g)
        dp = jnp.einsum("bqkgh,bskh->bkgqs", do_g, v_blk.astype(jnp.float32))
        d_g = d_span.reshape(B, span_q, KVH, G).transpose(0, 2, 3, 1)
        ds = probs * (dp - d_g[..., None]) * scale
        q_g = q_span.reshape(B, span_q, KVH, G, hd).astype(jnp.float32)
        dk_blk = jnp.einsum("bkgqs,bqkgh->bskh", ds, q_g)
        dk_blk = constrain(dk_blk.astype(k.dtype), "batch", None, "model", None)
        dv_blk = constrain(dv_blk.astype(v.dtype), "batch", None, "model", None)
        return None, (dk_blk, dv_blk)

    if nq == 1:
        _, dq = dq_block(None, 0)
    else:
        _, dq_blocks = jax.lax.scan(dq_block, None, jnp.arange(nq))
        dq = dq_blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)
    if nkv == 1:
        _, (dk, dv) = dkv_block(None, 0)
    else:
        _, (dk_blocks, dv_blocks) = jax.lax.scan(dkv_block, None, jnp.arange(nkv))
        dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, spec["S_kv"], KVH, hd)
        dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, spec["S_kv"], KVH, hd_v)
    # pin batch sharding at the custom_vjp boundary so upstream (rope/proj)
    # backward ops inherit it instead of all-gathering the batch dim
    dq = constrain(dq, "batch", None, "model", None)
    dk = constrain(dk, "batch", None, "model", None)
    dv = constrain(dv, "batch", None, "model", None)
    return dq, dk, dv


_SPEC_CACHE: dict = {}


def _flash_impl(spec_key, q, k, v):
    spec = _SPEC_CACHE[spec_key]
    out, _ = _flash_forward(spec, q, k, v)
    return out


def _flash_fwd_rule(spec_key, q, k, v):
    spec = _SPEC_CACHE[spec_key]
    out, lse = _flash_forward(spec, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(spec_key, res, dout):
    spec = _SPEC_CACHE[spec_key]
    dq, dk, dv = _flash_backward(spec, res, dout)
    return dq, dk, dv


_flash = jax.custom_vjp(_flash_impl, nondiff_argnums=(0,))
_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def blockwise_attention(
    q,
    k,
    v,
    *,
    attn_type: str = ATTN_FULL,
    window: int = 0,
    chunk: int = 0,
    causal: bool = True,
    block_q: int = 512,
    scale: Optional[float] = None,
):
    """Flash-style attention with a recompute backward.
    q: (B, S, H, hd); k/v: (B, S_kv, KVH, hd) — S_kv != S for cross-attn."""
    S, hd = q.shape[1], q.shape[-1]
    if attn_type == ATTN_CHUNKED_LOCAL and chunk and S > chunk and S % chunk == 0:
        # chunks are mutually invisible: scan over chunks running full-causal
        # flash within each. All slice starts are static, so the SPMD
        # partitioner keeps every buffer batch-sharded (a traced chunk start
        # forces a batch all-gather in the slice transpose).
        B, _, H, _ = q.shape
        nc = S // chunk

        def to_chunks(t):
            return t.reshape(B, nc, chunk, *t.shape[2:]).transpose(1, 0, 2, 3, 4)

        def body(_, qkv_c):
            q_c, k_c, v_c = qkv_c
            out = blockwise_attention(
                q_c, k_c, v_c, attn_type=ATTN_FULL, causal=causal,
                block_q=block_q, scale=scale,
            )
            return None, out

        _, outs = jax.lax.scan(body, None, (to_chunks(q), to_chunks(k), to_chunks(v)))
        return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, -1)
    spec = _resolve_spec(S, k.shape[1], attn_type, window, chunk, causal, block_q, scale, hd)
    key = tuple(sorted(spec.items()))
    _SPEC_CACHE[key] = spec
    return _flash(key, q, k, v)


# ---------------------------------------------------------------------------
# decode attention: one query token vs KV cache
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, valid_mask, scale: Optional[float] = None):
    """q: (B, 1, H, hd); k/v_cache: (B, Sc, KVH, hd); valid_mask: (B, Sc) bool."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    scores = _grouped_scores(q, k_cache, scale)  # (B,KVH,G,1,Sc)
    scores = jnp.where(valid_mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _grouped_out(probs, v_cache, q.dtype)  # (B,1,H,hd)


def cache_validity(attn_type: str, cache_len: int, pos, chunk: int = 0):
    """Which cache slots a decode query may attend, given absolute position
    ``pos`` of the new token. Ring caches (SWA) are fully valid once wrapped;
    chunked-local restricts to the current chunk."""
    slots = jnp.arange(cache_len)

    def _mask(p):
        m = slots <= jnp.minimum(p, cache_len - 1)  # filled so far (linear fill)
        if attn_type == ATTN_SWA:
            # ring cache: once wrapped (p+1 >= cache_len) every slot is valid
            m = jnp.where(p + 1 >= cache_len, jnp.ones_like(m), m)
        if attn_type == ATTN_CHUNKED_LOCAL and chunk:
            # ring of size `chunk`: valid slots = tokens in current chunk
            n_in_chunk = p % chunk + 1
            # slot indices are a ring; the newest n_in_chunk entries are valid
            age = (p % cache_len - slots) % cache_len
            m = age < n_in_chunk
        return m

    return jax.vmap(_mask)(pos) if jnp.ndim(pos) else _mask(pos)[None]


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention) — MiniCPM3 / DeepSeek style
# ---------------------------------------------------------------------------


def init_mla(key, cfg, dtype):
    ks = jax.random.split(key, 6)
    H = cfg.num_heads
    qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {
        "wq_a": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "q_norm": jnp.ones((cfg.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], cfg.q_lora_rank, H * qk_head, dtype),
        "wkv_a": dense_init(ks[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        "wkv_b": dense_init(
            ks[3], cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype
        ),
        "wo": dense_init(ks[4], H * cfg.v_head_dim, cfg.d_model, dtype),
    }
    return p


def mla_latents(params, x, cfg, positions):
    """Project x to the compressed MLA cache entries: c_kv and roped k_rope."""
    kv_a = x @ params["wkv_a"]  # (B,S,kv_lora+rope)
    c_kv = rms_norm(kv_a[..., : cfg.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., cfg.kv_lora_rank:][:, :, None, :]  # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return c_kv, k_rope


def mla_queries(params, x, cfg, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    cq = rms_norm(x @ params["wq_a"], params["q_norm"], cfg.norm_eps)
    q = (cq @ params["wq_b"]).reshape(B, S, H, qk_head)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_prefill(params, x, cfg, positions):
    """Full (expanded) MLA attention for prefill/training. Returns output and
    the compressed cache entries (c_kv, k_rope)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = mla_queries(params, x, cfg, positions)
    c_kv, k_rope = mla_latents(params, x, cfg, positions)

    kv = (c_kv @ params["wkv_b"]).reshape(B, S, H, cfg.qk_nope_head_dim + cfg.v_head_dim)
    k_nope = kv[..., : cfg.qk_nope_head_dim]
    v = kv[..., cfg.qk_nope_head_dim:]

    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, cfg.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    # pad v head dim up to qk head dim so blockwise core can be reused
    out = blockwise_attention(q, k, v, attn_type=ATTN_FULL, scale=scale)
    out = out.reshape(B, S, H * cfg.v_head_dim)
    return out @ params["wo"], (c_kv, k_rope)


def mla_decode(params, x, cfg, c_kv_cache, k_rope_cache, pos):
    """Absorbed-matrix MLA decode (TPU-native adaptation): queries move into
    the latent space so the cache is read once, with no per-step expansion.

    x: (B, 1, D); c_kv_cache: (B, Sc, kv_lora); k_rope_cache: (B, Sc, rope).
    """
    B = x.shape[0]
    H = cfg.num_heads
    Sc = c_kv_cache.shape[1]
    if jnp.ndim(pos) == 0:
        positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    else:
        positions = pos[:, None].astype(jnp.int32)
    q_nope, q_rope = mla_queries(params, x, cfg, positions)  # (B,1,H,nope/rope)

    w_b = params["wkv_b"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim)
    w_uk = w_b[..., : cfg.qk_nope_head_dim]  # (kv_lora, H, nope)
    w_uv = w_b[..., cfg.qk_nope_head_dim:]  # (kv_lora, H, v)

    q_lat = jnp.einsum("bqhn,khn->bqhk", q_nope, w_uk)  # (B,1,H,kv_lora)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    scores = (
        jnp.einsum("bqhk,bsk->bhqs", q_lat, c_kv_cache, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhr,bsr->bhqs", q_rope, k_rope_cache[:, :, 0, :]
                     if k_rope_cache.ndim == 4 else k_rope_cache,
                     preferred_element_type=jnp.float32)
    ) * scale
    if jnp.ndim(pos) == 0:
        valid = (jnp.arange(Sc) <= pos)[None, None, None, :]
    else:
        valid = (jnp.arange(Sc)[None] <= pos[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhqs,bsk->bqhk", probs.astype(c_kv_cache.dtype), c_kv_cache)
    out = jnp.einsum("bqhk,khv->bqhv", out_lat, w_uv)  # (B,1,H,v)
    out = out.reshape(B, 1, H * cfg.v_head_dim)
    return out @ params["wo"]
