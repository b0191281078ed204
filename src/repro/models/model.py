"""Top-level model API: init / forward / train_step / prefill / decode.

Every architecture exposes the same five entry points, so the serving engine,
launcher and dry-run treat the zoo uniformly:

    params            = init_params(cfg, key)
    logits, aux       = forward(cfg, params, batch)
    loss, metrics     = loss_fn(cfg, params, batch)
    logits, cache     = prefill(cfg, params, batch)
    logits, cache     = decode_step(cfg, params, cache, tokens, pos)

Batch layout per family:
    text (dense/moe/ssm/hybrid):  {"tokens": (B, S)}
    vlm:    {"tokens": (B, S - P), "patch_embeds": (B, P, D)}   (stub frontend)
    audio:  {"tokens": (B, S), "frames": (B, enc_seq, D)}       (stub frontend)
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN_MLA,
    ATTN_SWA,
    MIXER_HYBRID,
    MIXER_RWKV6,
    ModelConfig,
    ShapeConfig,
)
from repro.models import transformer as tfm
from repro.models.layers import (
    dense_init,
    embed_tokens,
    init_embed,
    sinusoidal_positions,
    unembed,
)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": init_embed(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
        "blocks": tfm._stack_layers(cfg, ks[1], dtype),
        "final_norm": tfm.init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(ks[2], cfg.d_model, cfg.padded_vocab, dtype, scale=0.02)}
    if cfg.num_meta_tokens:
        params["meta_tokens"] = (
            jax.random.normal(ks[3], (cfg.num_meta_tokens, cfg.d_model), jnp.float32) * 0.02
        ).astype(dtype)
    if cfg.num_patch_tokens:
        params["patch_proj"] = {"w": dense_init(ks[4], cfg.d_model, cfg.d_model, dtype)}
    if cfg.is_encoder_decoder:
        params["enc_blocks"] = tfm._stack_layers(cfg, ks[5], dtype, encoder=True)
        params["enc_final_norm"] = tfm.init_norm(cfg, dtype)
        params["frame_proj"] = {"w": dense_init(ks[6], cfg.d_model, cfg.d_model, dtype)}
    return params


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# embedding assembly (handles stub frontends + meta tokens)
# ---------------------------------------------------------------------------


def _embed_inputs(cfg, params, batch) -> Tuple[jnp.ndarray, int]:
    """Returns (x (B, S_total, D), n_prefix) where the first n_prefix positions
    are non-text (meta tokens / patch embeddings)."""
    x = embed_tokens(params["embed"], batch["tokens"])
    n_prefix = 0
    if cfg.num_patch_tokens and "patch_embeds" in batch:
        patches = batch["patch_embeds"].astype(x.dtype) @ params["patch_proj"]["w"]
        x = jnp.concatenate([patches, x], axis=1)
        n_prefix = patches.shape[1]
    if cfg.num_meta_tokens and "meta_tokens" in params:
        B = x.shape[0]
        meta = jnp.broadcast_to(
            params["meta_tokens"][None], (B, cfg.num_meta_tokens, cfg.d_model)
        ).astype(x.dtype)
        x = jnp.concatenate([meta, x], axis=1)
        n_prefix = n_prefix + cfg.num_meta_tokens
    if cfg.is_encoder_decoder or not cfg.use_rope:
        if not cfg.attention_free:  # whisper: sinusoidal decoder positions
            S = x.shape[1]
            x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
    return x, n_prefix


def _encode(cfg, params, batch):
    frames = batch["frames"].astype(jnp.dtype(cfg.dtype)) @ params["frame_proj"]["w"]
    frames = frames + sinusoidal_positions(frames.shape[1], cfg.d_model)[None].astype(frames.dtype)
    B, Se = frames.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))
    enc, _, _ = tfm.run_stack_seq(cfg, params["enc_blocks"], frames, positions, False, encoder=True)
    return tfm.apply_norm(cfg, params["enc_final_norm"], enc)


# ---------------------------------------------------------------------------
# forward / loss / train
# ---------------------------------------------------------------------------


def forward(cfg, params, batch, want_cache: bool = False, logits_mode: str = "all"):
    from repro.models.sharding import constrain

    x, n_prefix = _embed_inputs(cfg, params, batch)
    x = constrain(x, "batch", None, None)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc_out = _encode(cfg, params, batch) if cfg.is_encoder_decoder else None
    x, caches, aux = tfm.run_stack_seq(cfg, params["blocks"], x, positions, want_cache, enc_out)
    x = tfm.apply_norm(cfg, params["final_norm"], x)
    if n_prefix:
        x = x[:, n_prefix:]
    if logits_mode == "last":
        # prefill only needs the next-token distribution; never materialize
        # the (B, S, V) logits tensor
        x = x[:, -1:]
    logits = unembed(params["embed"], params.get("lm_head"), x, cfg.tie_embeddings)
    if cfg.padded_vocab != cfg.vocab_size:  # mask pad-vocab logits
        pad_bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size, 0.0, -1e30)
        logits = logits + pad_bias.astype(logits.dtype)
    logits = constrain(logits, "batch", None, "model")
    if want_cache:
        return logits, aux, caches
    return logits, aux


def loss_fn(cfg, params, batch):
    logits, aux = forward(cfg, params, batch)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = batch["tokens"][:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux, "total": total}


def make_train_step(cfg, optimizer, microbatches: int = 1, grad_shardings=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches > 1`` splits the global batch and accumulates gradients
    (f32) over a scan — the production knob that bounds remat-saved
    activation stacks to one microbatch. ``grad_shardings`` (a NamedSharding
    tree matching params) pins the f32 accumulator's sharding; without it the
    partitioner may replicate the accumulator across the pod axis."""

    def _pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, grad_shardings)

    def grads_of(params, batch):
        return jax.value_and_grad(lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if microbatches <= 1:
            (_, metrics), grads = grads_of(params, batch)
        else:
            ub = jax.tree.map(
                lambda t: t.reshape(microbatches, t.shape[0] // microbatches, *t.shape[1:]),
                batch,
            )

            def acc_body(acc, ubatch):
                (_, m), g = grads_of(params, ubatch)
                acc = _pin(jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32), acc, g))
                return acc, m

            zeros = _pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
            grads, ms = jax.lax.scan(acc_body, zeros, ub)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: jnp.mean(m), ms)
        params, opt_state = optimizer.update(params, grads, opt_state)
        metrics["grad_norm"] = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
        )
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(cfg, params, batch):
    """Run the prompt through the model, returning last-position logits and
    the serve cache."""
    logits, _, caches = forward(cfg, params, batch, want_cache=True, logits_mode="last")
    return logits[:, -1], caches


def prefill_packed(cfg, params, k_pool, v_pool, tables, tokens, row_of, slots,
                   positions, p_end, s_start, *, block_size, null_block,
                   impl="reference", interpret=True, k_scales=None,
                   v_scales=None):
    """Ragged fused step: T packed tokens (decode rows + prefill chunks from
    different sequences, no chunk-width padding) run against the paged pool
    directly. tokens/row_of/slots/positions/p_end/s_start: (T,) — see
    ``transformer.apply_layer_paged`` for the layout contract; tables: (B,
    mb) RAW block tables. Returns (logits (T, V), k_pool, v_pool, k_scales,
    v_scales); scales are None unless the pool is int8-quantized.

    ``impl="pallas"`` reads attention through ``kernels.paged_chunk_attention``
    (scalar-prefetched block streaming); ``"reference"`` is the jnp gather
    oracle. Both write the packed K/V into the pool before attending, so
    the pool comes back ready for the next plan. Quantized pools pass
    ``k_scales``/``v_scales`` (L, n_blocks, KVH) running absmax scales:
    writes requantize through ``write_paged_packed_q`` and both attention
    impls dequantize at read. Requires ``paged_cache_supported``
    (full-attention GQA, rope, period 1)."""
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens[None])      # (1, T, D)
    with jax.named_scope("layers"):
        x, k_pool, v_pool, k_scales, v_scales = tfm.run_stack_paged(
            cfg, params["blocks"], x, k_pool, v_pool, tables, row_of, slots,
            positions, p_end, s_start, block_size=block_size,
            null_block=null_block, impl=impl, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    with jax.named_scope("head"):
        x = tfm.apply_norm(cfg, params["final_norm"], x)
        logits = unembed(params["embed"], params.get("lm_head"), x,
                         cfg.tie_embeddings)
        if cfg.padded_vocab != cfg.vocab_size:  # mask pad-vocab logits (as forward)
            pad_bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                                 0.0, -1e30)
            logits = logits + pad_bias.astype(logits.dtype)
    return logits[0], k_pool, v_pool, k_scales, v_scales


def decode_step_paged(cfg, params, k_pool, v_pool, tables, tokens, pos, *,
                      block_size, null_block, interpret=True, k_scales=None,
                      v_scales=None):
    """Pallas-native paged decode: one new token per row attends its block
    chain in place (``kernels.paged_decode_attention``), no contiguous view
    gather. tokens: (B, 1); pos: (B,). Returns (logits (B, V), k_pool,
    v_pool, k_scales, v_scales); scales are None unless the pool is
    int8-quantized, in which case the kernel dequantizes per-block in VMEM.
    Requires ``paged_cache_supported``."""
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens)
    with jax.named_scope("layers"):
        x, k_pool, v_pool, k_scales, v_scales = tfm.run_stack_decode_paged(
            cfg, params["blocks"], x, k_pool, v_pool, tables, pos,
            block_size=block_size, null_block=null_block, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    with jax.named_scope("head"):
        x = tfm.apply_norm(cfg, params["final_norm"], x)
        logits = unembed(params["embed"], params.get("lm_head"), x,
                         cfg.tie_embeddings)
    return logits[:, 0], k_pool, v_pool, k_scales, v_scales


def paged_cache_supported(cfg: ModelConfig) -> bool:
    """Whether the paged serving path (block-table decode + chunked prefill +
    prefix sharing) supports this architecture: a homogeneous full-attention
    GQA decoder with rope positions and a plain token frontend. Everything
    else (MLA latents, recurrent/hybrid state, ring SWA caches, enc-dec,
    meta/patch prefixes) keeps the dense engine."""
    from repro.configs.base import ATTN_FULL

    return (
        tfm.period(cfg) == 1
        and cfg.attn_type == ATTN_FULL
        and cfg.use_rope
        and not cfg.is_encoder_decoder
        and not cfg.num_meta_tokens
        and not cfg.num_patch_tokens
    )


def decode_step(cfg, params, caches, tokens, pos):
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 absolute
    position of the new token. Returns (logits (B, V), new caches)."""
    x = embed_tokens(params["embed"], tokens)
    if (cfg.is_encoder_decoder or not cfg.use_rope) and not cfg.attention_free:
        if jnp.ndim(pos) == 0:
            pe = _sinusoidal_at(pos, cfg.d_model)[None, None, :]
        else:
            pe = jax.vmap(lambda p: _sinusoidal_at(p, cfg.d_model))(pos)[:, None, :]
        x = x + pe.astype(x.dtype)
    x, new_caches = tfm.run_stack_decode(cfg, params["blocks"], x, caches, pos)
    x = tfm.apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], params.get("lm_head"), x, cfg.tie_embeddings)
    return logits[:, 0], new_caches


def _sinusoidal_at(pos, d_model):
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)
    angle = pos.astype(jnp.float32) / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((d_model,), jnp.float32)
    pe = pe.at[0::2].set(jnp.sin(angle))
    pe = pe.at[1::2].set(jnp.cos(angle))
    return pe


# ---------------------------------------------------------------------------
# cache allocation (for dry-run decode shapes and the serving engine)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, B: int, S: int):
    """Zero-initialized serve cache sized for a context of S tokens."""
    dtype = jnp.dtype(cfg.dtype)
    p = tfm.period(cfg)
    G = cfg.num_layers // p

    def entry(pos):
        kind = tfm.layer_kind(cfg, pos)
        at = kind["attn_type"]
        if at == MIXER_RWKV6:
            hd = cfg.rwkv_head_dim
            H = cfg.d_model // hd
            return {
                "state": jnp.zeros((G, B, H, hd, hd), jnp.float32),
                "x_prev_att": jnp.zeros((G, B, cfg.d_model), dtype),
                "x_prev_ffn": jnp.zeros((G, B, cfg.d_model), dtype),
            }
        Sc = tfm.cache_len_for(cfg, kind, S)
        if at == ATTN_MLA:
            e = {
                "c_kv": jnp.zeros((G, B, Sc, cfg.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((G, B, Sc, cfg.qk_rope_head_dim), dtype),
            }
        else:
            kv_dt = jnp.int8 if cfg.kv_cache_quant else dtype
            e = {
                "k": jnp.zeros((G, B, Sc, cfg.num_kv_heads, cfg.head_dim), kv_dt),
                "v": jnp.zeros((G, B, Sc, cfg.num_kv_heads, cfg.head_dim), kv_dt),
            }
            if cfg.kv_cache_quant:  # per-slot, per-KV-head absmax scales
                e["k_scale"] = jnp.zeros((G, B, Sc, cfg.num_kv_heads), jnp.float32)
                e["v_scale"] = jnp.zeros((G, B, Sc, cfg.num_kv_heads), jnp.float32)
        if at == MIXER_HYBRID:
            e["conv"] = jnp.zeros((G, B, cfg.ssm_conv - 1, cfg.d_model), dtype)
            e["h"] = jnp.zeros((G, B, cfg.d_model, cfg.ssm_state), jnp.float32)
        if cfg.is_encoder_decoder:
            e["ck"] = jnp.zeros((G, B, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype)
            e["cv"] = jnp.zeros((G, B, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype)
        return e

    return tuple(entry(pos) for pos in range(p))


def abstract_cache(cfg, B, S):
    return jax.eval_shape(lambda: init_cache(cfg, B, S))


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Model inputs for a given assigned shape, as ShapeDtypeStructs."""
    B = shape.global_batch
    S = shape.seq_len
    sd = jax.ShapeDtypeStruct
    dtype = jnp.dtype(cfg.dtype)

    if shape.kind == "decode":
        return {"tokens": sd((B, 1), jnp.int32)}

    batch: Dict[str, Any] = {}
    if cfg.num_patch_tokens:
        batch["tokens"] = sd((B, S - cfg.num_patch_tokens), jnp.int32)
        batch["patch_embeds"] = sd((B, cfg.num_patch_tokens, cfg.d_model), dtype)
    else:
        batch["tokens"] = sd((B, S), jnp.int32)
    if cfg.is_encoder_decoder:
        batch["frames"] = sd((B, cfg.encoder_seq, cfg.d_model), dtype)
    return batch
