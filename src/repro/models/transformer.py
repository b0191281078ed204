"""Model assembly for all 10 assigned architectures.

A model is a stack of layers scanned over a *period* p of layer kinds
(llama4: [chunked, chunked, chunked, global] -> p=4; everything else p=1).
Per-period-position parameters are stacked over the L/p groups so the layer
stack lowers as a single ``lax.scan`` body — this keeps 512-device SPMD
compiles fast for 62-layer models. Heterogeneous serve-state (ring KV for
SWA/chunked layers, recurrent state for RWKV/SSM, compressed latents for
MLA) is carried as per-position cache trees with a leading group axis.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN_CHUNKED_LOCAL,
    ATTN_FULL,
    ATTN_MLA,
    ATTN_SWA,
    MIXER_HYBRID,
    MIXER_RWKV6,
    ModelConfig,
)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_mlp,
    dense_init,
    embed_tokens,
    init_embed,
    init_mlp,
    layer_norm,
    rms_norm,
    zeros_init,
)

# ---------------------------------------------------------------------------
# layer-kind resolution
# ---------------------------------------------------------------------------


def period(cfg: ModelConfig) -> int:
    return cfg.global_layer_every if cfg.global_layer_every else 1


def layer_kind(cfg: ModelConfig, layer: int) -> Dict[str, Any]:
    return {
        "attn_type": cfg.layer_attn_type(layer),
        "moe": cfg.layer_is_moe(layer),
        "cross": cfg.is_encoder_decoder,
    }


def cache_len_for(cfg: ModelConfig, kind: Dict[str, Any], S: int) -> int:
    at = kind["attn_type"]
    if at == ATTN_SWA:
        return min(S, cfg.window)
    if at == ATTN_CHUNKED_LOCAL:
        return min(S, cfg.chunk_size)
    return S


def _uses_layernorm(cfg: ModelConfig) -> bool:
    return cfg.attn_type == MIXER_RWKV6 or cfg.is_encoder_decoder


def init_norm(cfg, dtype):
    if _uses_layernorm(cfg):
        return {"scale": jnp.ones((cfg.d_model,), dtype), "bias": zeros_init((cfg.d_model,), dtype)}
    return {"scale": jnp.ones((cfg.d_model,), dtype)}


def apply_norm(cfg, p, x):
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, kind: Dict[str, Any], dtype, encoder: bool = False):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"norm1": init_norm(cfg, dtype)}
    at = kind["attn_type"] if not encoder else ATTN_FULL

    if at == MIXER_RWKV6:
        p["rwkv"] = rwkv_mod.init_rwkv6(ks[0], cfg, dtype)
        p["norm2"] = init_norm(cfg, dtype)
        p["rwkv_ffn"] = rwkv_mod.init_rwkv6_ffn(ks[1], cfg, dtype)
        return p

    if at == ATTN_MLA:
        p["attn"] = attn.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = attn.init_attention(
            ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.qkv_bias, dtype,
        )
    if at == MIXER_HYBRID:
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg, dtype)
        p["gate_attn"] = jnp.ones((cfg.d_model,), dtype)
        p["gate_ssm"] = jnp.ones((cfg.d_model,), dtype)

    if kind["cross"] and not encoder:
        p["cross_norm"] = init_norm(cfg, dtype)
        p["cross_attn"] = attn.init_attention(
            ks[2], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            False, dtype,
        )

    p["norm2"] = init_norm(cfg, dtype)
    if kind["moe"] and not encoder:
        p["moe"] = moe_mod.init_moe(ks[3], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


# ---------------------------------------------------------------------------
# per-layer apply: sequence mode (train / prefill)
# ---------------------------------------------------------------------------


def _attn_branch_seq(cfg, lp, xn, positions, attn_type, want_cache, S):
    from repro.models.layers import apply_rope
    from repro.models.sharding import constrain

    q, k, v = attn.qkv_project(lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    out = attn.blockwise_attention(
        q, k, v, attn_type=attn_type, window=cfg.window, chunk=cfg.chunk_size,
    )
    B = xn.shape[0]
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim) @ lp["attn"]["wo"]
    cache = None
    if want_cache:
        Sc = cache_len_for(cfg, {"attn_type": attn_type}, S)
        cache = {"k": k[:, S - Sc :], "v": v[:, S - Sc :]}
        if cfg.kv_cache_quant:
            qk, sk = _quantize_kv(cache["k"])
            qv, sv = _quantize_kv(cache["v"])
            cache = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    return out, cache


def apply_layer_seq(cfg, kind, lp, x, positions, want_cache, enc_out=None):
    """x: (B,S,D) -> (x, cache_entry, aux_loss)."""
    B, S, D = x.shape
    at = kind["attn_type"]
    aux = jnp.zeros((), jnp.float32)

    if at == MIXER_RWKV6:
        xn = apply_norm(cfg, lp["norm1"], x)
        out, (xprev_a, state) = rwkv_mod.apply_rwkv6(lp["rwkv"], xn, cfg)
        x = x + out
        xn2 = apply_norm(cfg, lp["norm2"], x)
        ffn_out, xprev_f = rwkv_mod.apply_rwkv6_ffn(lp["rwkv_ffn"], xn2)
        x = x + ffn_out
        cache = (
            {"state": state, "x_prev_att": xprev_a, "x_prev_ffn": xprev_f}
            if want_cache
            else None
        )
        return x, cache, aux

    xn = apply_norm(cfg, lp["norm1"], x)
    if at == ATTN_MLA:
        out, (c_kv, k_rope) = attn.mla_prefill(lp["attn"], xn, cfg, positions)
        cache = {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]} if want_cache else None
    elif at == MIXER_HYBRID:
        a_out, a_cache = _attn_branch_seq(cfg, lp, xn, positions, ATTN_SWA, want_cache, S)
        s_out, (conv_tail, h) = ssm_mod.apply_ssm(lp["ssm"], xn, cfg)
        out = 0.5 * (
            rms_norm(a_out, lp["gate_attn"], cfg.norm_eps)
            + rms_norm(s_out, lp["gate_ssm"], cfg.norm_eps)
        )
        cache = None
        if want_cache:
            cache = dict(a_cache)
            cache["conv"] = conv_tail
            cache["h"] = h
    else:
        out, cache = _attn_branch_seq(cfg, lp, xn, positions, at, want_cache, S)
    x = x + out

    if "cross_attn" in lp and enc_out is not None:
        xn = apply_norm(cfg, lp["cross_norm"], x)
        q, _, _ = attn.qkv_project(lp["cross_attn"], xn, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
        _, ck, cv = attn.qkv_project(lp["cross_attn"], enc_out, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
        c_out = attn.blockwise_attention(q, ck, cv, attn_type=ATTN_FULL, causal=False)
        x = x + c_out.reshape(B, S, -1) @ lp["cross_attn"]["wo"]
        if want_cache and cache is not None:
            cache["ck"], cache["cv"] = ck, cv
        elif want_cache:
            cache = {"ck": ck, "cv": cv}

    xn = apply_norm(cfg, lp["norm2"], x)
    if "moe" in lp:
        ffn_out, aux = moe_mod.apply_moe(lp["moe"], xn, cfg)
    else:
        ffn_out = apply_mlp(lp["mlp"], xn, cfg.act)
    return x + ffn_out, cache, aux


# ---------------------------------------------------------------------------
# per-layer apply: decode mode (one token against cache)
# ---------------------------------------------------------------------------


def _quantize_kv(x):
    """Symmetric int8 KV quantization with per-slot, per-KV-head absmax
    scales (halves the HBM cache-read traffic that dominates the decode
    roofline). Same convention as the paged pools' per-(block, KV-head)
    scales — the dense cache's "block" is a single slot, so no running-max
    bookkeeping is needed: each slot is written exactly once.

    x: (B, C, KVH, hd) -> (int8 values, (B, C, KVH) float32 scales)."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.maximum(s, 1e-30)[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def _dequantize_kv(x, s, dtype):
    return (x.astype(jnp.float32) * s[..., None]).astype(dtype)


def _cache_update(c, new, pos):
    """Write new entries starting at pos % Sc. c: (B, Sc, ...); new: (B, C, ...).
    pos may be a scalar (dry-run serve_step / single-sequence chunked prefill)
    or (B,) per-row starts (continuous batching; the fused interleaved batch
    mixes decode rows with C-token prefill chunks at per-row positions)."""
    Sc = c.shape[1]
    new = new.astype(c.dtype)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(c, new, pos % Sc, 1)
    if new.shape[1] == 1:
        return c.at[jnp.arange(c.shape[0]), pos % Sc].set(new[:, 0])
    idx = (pos[:, None] + jnp.arange(new.shape[1])) % Sc
    return c.at[jnp.arange(c.shape[0])[:, None], idx].set(new)


def apply_layer_decode(cfg, kind, lp, x, cache, pos, enc_out_unused=None):
    """x: (B,1,D); cache: this layer's entry; pos: scalar or (B,) absolute
    position(s). Returns (x, new_cache)."""
    from repro.models.layers import apply_rope

    B = x.shape[0]
    at = kind["attn_type"]
    new_cache = dict(cache)

    if at == MIXER_RWKV6:
        xn = apply_norm(cfg, lp["norm1"], x)
        out, (xprev_a, state) = rwkv_mod.apply_rwkv6(
            lp["rwkv"], xn, cfg, x_prev_last=cache["x_prev_att"], state=cache["state"]
        )
        x = x + out
        xn2 = apply_norm(cfg, lp["norm2"], x)
        ffn_out, xprev_f = rwkv_mod.apply_rwkv6_ffn(lp["rwkv_ffn"], xn2, cache["x_prev_ffn"])
        x = x + ffn_out
        new_cache.update(state=state, x_prev_att=xprev_a, x_prev_ffn=xprev_f)
        return x, new_cache

    xn = apply_norm(cfg, lp["norm1"], x)
    if jnp.ndim(pos) == 0:
        positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    else:
        positions = pos[:, None].astype(jnp.int32)

    if at == ATTN_MLA:
        c_kv_new, k_rope_new = attn.mla_latents(lp["attn"], xn, cfg, positions)
        c_kv = _cache_update(cache["c_kv"], c_kv_new, pos)
        k_rope = _cache_update(cache["k_rope"], k_rope_new[:, :, 0, :], pos)
        out = attn.mla_decode(lp["attn"], xn, cfg, c_kv, k_rope, pos)
        new_cache.update(c_kv=c_kv, k_rope=k_rope)
        x = x + out
    else:
        q, k, v = attn.qkv_project(lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        eff_at = ATTN_SWA if at == MIXER_HYBRID else at
        Sc = cache["k"].shape[1]
        if cfg.kv_cache_quant:
            qk, sk = _quantize_kv(k)
            qv, sv = _quantize_kv(v)
            kc = _cache_update(cache["k"], qk, pos)
            vc = _cache_update(cache["v"], qv, pos)
            ksc = _cache_update(cache["k_scale"], sk, pos)
            vsc = _cache_update(cache["v_scale"], sv, pos)
            k_read = _dequantize_kv(kc, ksc, q.dtype)
            v_read = _dequantize_kv(vc, vsc, q.dtype)
            new_cache.update(k_scale=ksc, v_scale=vsc)
        else:
            kc = _cache_update(cache["k"], k, pos)
            vc = _cache_update(cache["v"], v, pos)
            k_read, v_read = kc, vc
        valid = attn.cache_validity(eff_at, Sc, pos, cfg.chunk_size)
        valid = jnp.broadcast_to(valid, (B, Sc))
        a_out = attn.decode_attention(q, k_read, v_read, valid)
        a_out = a_out.reshape(B, 1, cfg.num_heads * cfg.head_dim) @ lp["attn"]["wo"]
        new_cache.update(k=kc, v=vc)
        if at == MIXER_HYBRID:
            s_out, (conv_tail, h) = ssm_mod.apply_ssm(
                lp["ssm"], xn, cfg, conv_tail=cache["conv"], h0=cache["h"]
            )
            out = 0.5 * (
                rms_norm(a_out, lp["gate_attn"], cfg.norm_eps)
                + rms_norm(s_out, lp["gate_ssm"], cfg.norm_eps)
            )
            new_cache.update(conv=conv_tail, h=h)
        else:
            out = a_out
        x = x + out

    if "cross_attn" in lp:
        xn2 = apply_norm(cfg, lp["cross_norm"], x)
        q, _, _ = attn.qkv_project(lp["cross_attn"], xn2, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
        Sc = cache["ck"].shape[1]
        valid = jnp.ones((B, Sc), bool)
        c_out = attn.decode_attention(q, cache["ck"], cache["cv"], valid)
        x = x + c_out.reshape(B, 1, -1) @ lp["cross_attn"]["wo"]

    xn = apply_norm(cfg, lp["norm2"], x)
    if "moe" in lp:
        ffn_out, _ = moe_mod.apply_moe(lp["moe"], xn, cfg)
    else:
        ffn_out = apply_mlp(lp["mlp"], xn, cfg.act)
    return x + ffn_out, new_cache


# ---------------------------------------------------------------------------
# per-layer apply: chunked prefill mode (C tokens against a cached prefix)
# ---------------------------------------------------------------------------


def apply_layer_prefix(cfg, kind, lp, x, cache, pos, positions=None,
                       seg_prefix_end=None, seg_start=None):
    """Chunked prefill: x (B,C,D) of prompt tokens at cache slots
    ``pos .. pos+C-1`` attends the cached prefix plus itself (causal). The
    chunk's K/V entries are written into the cache before attention, so the
    returned cache is ready for the next chunk or for decode. ``pos`` is a
    scalar (all rows aligned) or (B,) per-row starts — the fused interleaved
    batch runs every row at its own cursor, decode rows included (C-padded
    chunks of one valid token).

    Segmented prompts (retrieval-aware prefix caching) decouple a token's
    RoPE position and attention span from its cache slot: ``positions``
    (B,C) overrides the rope positions (document segments restart at the
    prelude length so their K/V is order-independent), and the attention mask
    becomes ``slot < seg_prefix_end[t]  OR  seg_start[t] <= slot <= slot(t)``
    — document tokens attend the prelude plus their own segment only. The
    defaults (positions == slots, seg bounds 0) reproduce plain causal
    prefill bit-for-bit.

    Full-attention GQA stacks only (the paged serving path); other mixers keep
    the bucketed whole-prompt prefill."""
    from repro.models.layers import apply_rope

    B, C, _ = x.shape
    at = kind["attn_type"]
    if at != ATTN_FULL or kind["cross"]:
        raise NotImplementedError(
            "chunked prefix prefill supports full-attention GQA stacks only"
        )
    xn = apply_norm(cfg, lp["norm1"], x)
    if jnp.ndim(pos) == 0:
        slots = jnp.broadcast_to(
            (pos + jnp.arange(C)).astype(jnp.int32)[None], (B, C)
        )
    else:
        slots = (pos[:, None] + jnp.arange(C)[None, :]).astype(jnp.int32)
    if positions is None:
        positions = slots
    q, k, v = attn.qkv_project(lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    Sc = cache["k"].shape[1]
    new_cache = dict(cache)
    if cfg.kv_cache_quant:
        qk, sk = _quantize_kv(k)
        qv, sv = _quantize_kv(v)
        kc = _cache_update(cache["k"], qk, pos)
        vc = _cache_update(cache["v"], qv, pos)
        ksc = _cache_update(cache["k_scale"], sk, pos)
        vsc = _cache_update(cache["v_scale"], sv, pos)
        k_read = _dequantize_kv(kc, ksc, q.dtype)
        v_read = _dequantize_kv(vc, vsc, q.dtype)
        new_cache.update(k_scale=ksc, v_scale=vsc)
    else:
        kc = _cache_update(cache["k"], k, pos)
        vc = _cache_update(cache["v"], v, pos)
        k_read, v_read = kc, vc
    s = jnp.arange(Sc)[None, None, :]
    if seg_prefix_end is None:
        valid = s <= slots[:, :, None]  # (B,C,Sc) plain causal over slots
    else:
        valid = (s < seg_prefix_end[:, :, None]) | (
            (s >= seg_start[:, :, None]) & (s <= slots[:, :, None])
        )
    a_out = attn.chunk_decode_attention(q, k_read, v_read, valid)
    x = x + a_out.reshape(B, C, cfg.num_heads * cfg.head_dim) @ lp["attn"]["wo"]
    new_cache.update(k=kc, v=vc)

    xn = apply_norm(cfg, lp["norm2"], x)
    if "moe" in lp:
        ffn_out, _ = moe_mod.apply_moe(lp["moe"], xn, cfg)
    else:
        ffn_out = apply_mlp(lp["mlp"], xn, cfg.act)
    return x + ffn_out, new_cache


# ---------------------------------------------------------------------------
# stack runner
# ---------------------------------------------------------------------------


def _stack_layers(cfg, key, dtype, encoder=False):
    """Init decoder (or encoder) layers stacked into period groups."""
    L = cfg.encoder_layers if encoder else cfg.num_layers
    p = 1 if encoder else period(cfg)
    G = L // p
    keys = jax.random.split(key, L)
    blocks: List[Any] = []
    for pos in range(p):
        kind = layer_kind(cfg, pos)
        per_group = [
            init_layer(keys[g * p + pos], cfg, kind, dtype, encoder) for g in range(G)
        ]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))
    return blocks


def run_stack_seq(cfg, blocks, x, positions, want_cache, enc_out=None, encoder=False):
    """Scan the layer stack over groups. Returns (x, caches, aux_total)."""
    p = 1 if encoder else period(cfg)
    kinds = [
        {"attn_type": ATTN_FULL, "moe": False, "cross": False}
        if encoder
        else layer_kind(cfg, pos)
        for pos in range(p)
    ]

    def body(carry, block_slice):
        from repro.models.sharding import constrain

        x, aux = carry
        # Megatron-style sequence parallelism at the layer-group boundary
        # ONLY: the remat-saved carry shards (batch x seq-on-model) — cutting
        # saved-activation memory by the model-axis size — while inside the
        # body activations are batch-sharded, so the partitioner sees one
        # explicit all-gather/reduce-scatter pair per group instead of trying
        # to propagate seq-sharding through attention.
        x = constrain(x, "batch", None, None)
        caches = []
        for pos in range(p):
            x, cache, a = apply_layer_seq(
                cfg, kinds[pos], block_slice[pos], x, positions, want_cache, enc_out
            )
            x = constrain(x, "batch", None, None)
            aux = aux + a
            caches.append(cache)
        x = constrain(x, "batch", "model", None)
        return (x, aux), tuple(caches) if want_cache else None

    # remat: each layer group recomputes in backward; combined with the
    # flash-attention custom_vjp this keeps train memory O(B*S*D) per layer.
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    init = (x, jnp.zeros((), jnp.float32))
    G = jax.tree.leaves(blocks)[0].shape[0]
    seg = _segment_size(G)
    if seg > 1 and not want_cache:
        # two-level segmented scan (beyond-paper §Perf H1): the plain scan
        # saves the (B,S,D) carry for all G groups — O(G) residual stacks.
        # Scanning sqrt(G) segments of sqrt(G) groups saves outer carries +
        # one segment's inner carries: O(2*sqrt(G)), a ~G/(2*sqrt(G))x cut
        # in remat-stack memory for deep models (mixtral: 56 -> ~15 carries).
        n_seg = G // seg
        seg_blocks = jax.tree.map(
            lambda t: t.reshape(n_seg, seg, *t.shape[1:]), blocks
        )

        @jax.checkpoint
        def segment(carry, seg_slice):
            carry, _ = jax.lax.scan(body, carry, seg_slice)
            return carry, None

        (x, aux), _ = jax.lax.scan(segment, init, seg_blocks)
        return x, None, aux
    (x, aux), caches = jax.lax.scan(body, init, blocks)
    return x, caches, aux


def _segment_size(G: int) -> int:
    """Largest divisor of G closest to sqrt(G), if G is deep enough."""
    if G < 16:
        return 1
    best = 1
    for s in range(2, G):
        if G % s == 0 and abs(s - math.isqrt(G)) < abs(best - math.isqrt(G)):
            best = s
    return best


def run_stack_prefix(cfg, blocks, x, caches, pos, positions=None,
                     seg_prefix_end=None, seg_start=None):
    """Scan the layer stack in chunked-prefill mode: x (B,C,D) written into
    (and attending) the serve cache at absolute start slot ``pos`` — scalar,
    or (B,) per-row starts for the fused interleaved batch (the chunk must
    fit inside the cache, no ring wrap). ``positions``/``seg_prefix_end``/
    ``seg_start`` (all (B,C), optional) carry the segmented-prompt rope
    positions and attention spans; see ``apply_layer_prefix``."""
    p = period(cfg)
    kinds = [layer_kind(cfg, i) for i in range(p)]

    def body(x, slices):
        block_slice, cache_slice = slices
        new_caches = []
        for i in range(p):
            x, nc = apply_layer_prefix(
                cfg, kinds[i], block_slice[i], x, cache_slice[i], pos,
                positions, seg_prefix_end, seg_start,
            )
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(body, x, (blocks, caches))
    return x, new_caches


def apply_layer_paged(cfg, kind, lp, x, k_slice, v_slice, tables, row_of,
                      slots, positions, p_end, s_start, *, block_size,
                      null_block, k_sc=None, v_sc=None, impl="reference",
                      interpret=True):
    """Ragged fused-step layer: T packed tokens (decode rows and prefill
    chunks from different sequences, back to back in one flat buffer) read
    and write the paged pool DIRECTLY — no per-row contiguous view is ever
    materialized, and there are no chunk-width padding rows.

    x: (1, T, D); k/v_slice: (n_blocks, bs, KVH, hd) one layer group's pool;
    tables: (B, mb) int32 RAW block tables (-1 holes allowed); row_of/slots/
    positions/p_end/s_start: (T,) per-token owning row, absolute cache slot,
    rope position and segment-attention span (see ``apply_layer_prefix`` —
    the mask ``slot < p_end  OR  s_start <= slot <= own slot`` is identical,
    applied per packed token instead of per (row, chunk-col)).

    The chunk's K/V entries are scattered into the pool BEFORE attention
    (``write_paged_packed``), mirroring the chunked-prefill path, so each
    token's own entry — and every earlier packed token of the same row — is
    visible to its query. ``impl`` selects the attention read: "pallas"
    streams blocks through ``kernels.paged_chunk_attention``; "reference"
    gathers per-token views and runs the masked-softmax oracle (the numerics
    contract, and the path that keeps working under shard_map meshes).

    ``k_sc``/``v_sc`` ((n_blocks, KVH) float32, both or neither) mark an
    int8-quantized pool slice: writes quantize at scatter time
    (``write_paged_packed_q``, running-max per-block scales) and attention
    dequantizes inside the kernel (or after the oracle's gather).

    Full-attention GQA stacks only, like the rest of the paged path."""
    from repro.kernels.decode_attention import (
        paged_chunk_attention, ref_paged_chunk_attention,
    )
    from repro.models.layers import apply_rope
    from repro.serving.paged_cache import (
        write_paged_packed, write_paged_packed_q,
    )

    at = kind["attn_type"]
    if at != ATTN_FULL or kind["cross"]:
        raise NotImplementedError(
            "ragged paged prefill supports full-attention GQA stacks only"
        )
    with jax.named_scope("qkv"):
        xn = apply_norm(cfg, lp["norm1"], x)
        q, k, v = attn.qkv_project(
            lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        )
        if cfg.use_rope:
            q = apply_rope(q, positions[None], cfg.rope_theta)
            k = apply_rope(k, positions[None], cfg.rope_theta)
    with jax.named_scope("pool_write"):
        if k_sc is None:
            k_slice = write_paged_packed(
                k_slice, tables, row_of, slots, k[0], block_size, null_block
            )
            v_slice = write_paged_packed(
                v_slice, tables, row_of, slots, v[0], block_size, null_block
            )
        else:
            k_slice, k_sc = write_paged_packed_q(
                k_slice, k_sc, tables, row_of, slots, k[0], block_size,
                null_block
            )
            v_slice, v_sc = write_paged_packed_q(
                v_slice, v_sc, tables, row_of, slots, v[0], block_size,
                null_block
            )
    with jax.named_scope("attn"):
        if impl == "pallas":
            a_out = paged_chunk_attention(
                q[0], k_slice, v_slice, tables, row_of, slots, p_end, s_start,
                k_scale=k_sc, v_scale=v_sc, interpret=interpret,
            )
        else:
            a_out = ref_paged_chunk_attention(
                q[0], k_slice, v_slice, tables, row_of, slots, p_end, s_start,
                k_scale=k_sc, v_scale=v_sc,
            )
        T = x.shape[1]
        x = x + (a_out.reshape(1, T, cfg.num_heads * cfg.head_dim)
                 @ lp["attn"]["wo"])
    return _mlp_residual(cfg, lp, x), k_slice, v_slice, k_sc, v_sc


def _mlp_residual(cfg, lp, x):
    """The paged layers' second half: norm, MLP (or MoE), residual."""
    with jax.named_scope("mlp"):
        xn = apply_norm(cfg, lp["norm2"], x)
        if "moe" in lp:
            ffn_out, _ = moe_mod.apply_moe(lp["moe"], xn, cfg)
        else:
            ffn_out = apply_mlp(lp["mlp"], xn, cfg.act)
        return x + ffn_out


def run_stack_paged(cfg, blocks, x, k_pool, v_pool, tables, row_of, slots,
                    positions, p_end, s_start, *, block_size, null_block,
                    k_scales=None, v_scales=None, impl="reference",
                    interpret=True):
    """Scan the layer stack in ragged fused-step mode: x (1, T, D) packed
    tokens against the full paged pool (G, n_blocks, bs, KVH, hd). Each scan
    step consumes and re-emits one layer group's pool slice — the pool is
    both the KV source and the write destination, so no separate
    gather/extract/scatter phases exist. ``k_scales``/``v_scales``
    ((G, n_blocks, KVH) float32) ride the scan alongside an int8 pool; both
    are None for float pools. Returns (x, k_pool, v_pool, k_scales,
    v_scales)."""
    p = period(cfg)
    kinds = [layer_kind(cfg, i) for i in range(p)]
    assert p == 1, "ragged paged path requires period-1 stacks"

    def body(x, slices):
        block_slice, k_slice, v_slice, k_sc, v_sc = slices
        x, k_slice, v_slice, k_sc, v_sc = apply_layer_paged(
            cfg, kinds[0], block_slice[0], x, k_slice, v_slice, tables,
            row_of, slots, positions, p_end, s_start,
            block_size=block_size, null_block=null_block,
            k_sc=k_sc, v_sc=v_sc, impl=impl, interpret=interpret,
        )
        return x, (k_slice, v_slice, k_sc, v_sc)

    x, (k_pool, v_pool, k_scales, v_scales) = jax.lax.scan(
        body, x, (blocks, k_pool, v_pool, k_scales, v_scales)
    )
    return x, k_pool, v_pool, k_scales, v_scales


def apply_layer_decode_paged(cfg, kind, lp, x, k_slice, v_slice, tables, pos,
                             *, block_size, null_block, k_sc=None, v_sc=None,
                             interpret=True):
    """Pallas-native paged decode layer: write the new token's K/V into the
    pool slice, then stream the sequence's blocks through
    ``kernels.paged_decode_attention`` — no contiguous view gather. x:
    (B, 1, D); k/v_slice: (n_blocks, bs, KVH, hd); tables: (B, mb); pos:
    (B,) absolute position of the new token (rows must be table-backed at
    ``pos`` — the plan allocates before it decodes). A row the plan left
    empty has ``pos`` 0 (a live row has a prompt before its new token), so
    it attends nothing: the kernel gets length 0 for it and runs no cell.
    ``k_sc``/``v_sc`` ((n_blocks, KVH) float32) mark an int8 pool slice:
    the token's K/V quantizes at scatter time and the kernel dequantizes in
    VMEM."""
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.models.layers import apply_rope
    from repro.serving.paged_cache import _quantized_scatter

    at = kind["attn_type"]
    if at != ATTN_FULL or kind["cross"]:
        raise NotImplementedError(
            "paged pallas decode supports full-attention GQA stacks only"
        )
    B = x.shape[0]
    bs = block_size
    with jax.named_scope("qkv"):
        xn = apply_norm(cfg, lp["norm1"], x)
        q, k, v = attn.qkv_project(
            lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        )
        if cfg.use_rope:
            positions = pos[:, None].astype(jnp.int32)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    blk = tables[jnp.arange(B), pos // bs]
    dest = jnp.where(blk >= 0, blk * bs + pos % bs, null_block * bs)

    def scatter(pool, new):
        nb = pool.shape[0]
        flat = pool.reshape(nb * bs, *pool.shape[2:])
        return flat.at[dest].set(new.astype(flat.dtype)).reshape(pool.shape)

    def scatter_q(pool, sc, new):
        p, s = _quantized_scatter(pool[None], sc[None], dest, new[None])
        return p[0], s[0]

    with jax.named_scope("pool_write"):
        if k_sc is None:
            k_slice = scatter(k_slice, k[:, 0])
            v_slice = scatter(v_slice, v[:, 0])
        else:
            k_slice, k_sc = scatter_q(k_slice, k_sc, k[:, 0])
            v_slice, v_sc = scatter_q(v_slice, v_sc, v[:, 0])
    with jax.named_scope("attn"):
        lengths = jnp.where(pos > 0, pos + 1, 0)
        a_out = paged_decode_attention(
            q[:, 0], k_slice, v_slice, tables, lengths,
            k_scale=k_sc, v_scale=v_sc, interpret=interpret
        )
        x = x + (a_out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
                 @ lp["attn"]["wo"])
    return _mlp_residual(cfg, lp, x), k_slice, v_slice, k_sc, v_sc


def run_stack_decode_paged(cfg, blocks, x, k_pool, v_pool, tables, pos, *,
                           block_size, null_block, k_scales=None,
                           v_scales=None, interpret=True):
    """Scan the layer stack in pallas paged-decode mode: x (B, 1, D), pool
    (G, n_blocks, bs, KVH, hd), per-row positions (B,). ``k_scales``/
    ``v_scales`` ride the scan for int8 pools (None otherwise). Returns
    (x, k_pool, v_pool, k_scales, v_scales)."""
    p = period(cfg)
    kinds = [layer_kind(cfg, i) for i in range(p)]
    assert p == 1, "paged pallas decode requires period-1 stacks"

    def body(x, slices):
        block_slice, k_slice, v_slice, k_sc, v_sc = slices
        x, k_slice, v_slice, k_sc, v_sc = apply_layer_decode_paged(
            cfg, kinds[0], block_slice[0], x, k_slice, v_slice, tables, pos,
            block_size=block_size, null_block=null_block,
            k_sc=k_sc, v_sc=v_sc, interpret=interpret,
        )
        return x, (k_slice, v_slice, k_sc, v_sc)

    x, (k_pool, v_pool, k_scales, v_scales) = jax.lax.scan(
        body, x, (blocks, k_pool, v_pool, k_scales, v_scales)
    )
    return x, k_pool, v_pool, k_scales, v_scales


def run_stack_decode(cfg, blocks, x, caches, pos_scalar):
    p = period(cfg)
    kinds = [layer_kind(cfg, pos) for pos in range(p)]

    def body(x, slices):
        block_slice, cache_slice = slices
        new_caches = []
        for i in range(p):
            x, nc = apply_layer_decode(cfg, kinds[i], block_slice[i], x, cache_slice[i], pos_scalar)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(body, x, (blocks, caches))
    return x, new_caches
