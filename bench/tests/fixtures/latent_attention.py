"""Test fixture: the work counts of a decoder with latent attention and
routed experts (DeepSeek-V2 style), in the form a reference module gives
them to the per-layer metrics. Counts only: no forward pass.

Attention is counted in its absorbed form, as a serving step computes it
from a cache of one latent per slot: each head's query is taken into the
latent space, scores a key of ``kv_lora_rank + qk_rope_head_dim`` and sums
a value of ``kv_lora_rank`` at every slot it attends, and its output is
taken back to ``v_head_dim`` before the output projection. Per computed
token and layer (multiply-adds):

    q        d x H(nope + rope)          (no q_lora)
    absorb   H x nope x latent
    kv down  d x (latent + rope)
    scores   H x (latent + rope) per attended slot
    values   H x latent per attended slot
    unabsorb H x latent x v
    o        H x v x d
    MLP      3 d d_ff in the leading dense layers; in the others the router
             d x n_experts and 3 d moe_ff for each shared expert and each
             routed (expert, token) pair: ``plan["expert_pairs"]`` where an
             adapter reports them, else experts-per-token for every token

A paged attention kernel reads each attended slot's latent once, as key
and value, reads every query (H x (latent + rope)) and writes every output
(H x latent).
"""
from dataclasses import dataclass

import numpy as np

from bench.harness import work


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d_model: int
    d_ff: int
    moe_ff: int
    n_experts: int
    top_k: int
    shared: int
    heads: int
    nope: int
    rope: int
    latent: int
    v_head: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(layers=int(c["num_hidden_layers"]),
                   dense_layers=int(c["first_k_dense_replace"]),
                   d_model=int(c["hidden_size"]), d_ff=int(c["intermediate_size"]),
                   moe_ff=int(c["moe_intermediate_size"]),
                   n_experts=int(c["n_routed_experts"]), top_k=int(c["num_experts_per_tok"]),
                   shared=int(c["n_shared_experts"]), heads=int(c["num_attention_heads"]),
                   nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
                   latent=int(c["kv_lora_rank"]), v_head=int(c["v_head_dim"]),
                   vocab=int(c["vocab_size"]))


def _per_slot(d) -> int:
    return 2 * d.heads * (d.latent + d.rope + d.latent)


def _projections(d) -> int:
    H, dm = d.heads, d.d_model
    return 2 * (dm * H * (d.nope + d.rope) + H * d.nope * d.latent + dm * (d.latent + d.rope)
                + H * d.latent * d.v_head + H * d.v_head * dm)


def step_flops(d, plan):
    if plan["kind"] == "ragged":
        ctx = work.token_contexts(plan["slots"], plan["p_end"], plan["s_start"])
    elif plan["kind"] == "decode":
        ctx = np.asarray(plan["ctx"])
    else:
        return None
    n = len(ctx)
    pairs = plan.get("expert_pairs", n * d.top_k)
    experts = d.layers - d.dense_layers
    mlp = (d.dense_layers * n * 6 * d.d_model * d.d_ff
           + experts * (n * 2 * d.d_model * d.n_experts
                        + (n * d.shared + pairs) * 6 * d.d_model * d.moe_ff))
    return float(d.layers * (n * _projections(d) + _per_slot(d) * int(np.sum(ctx))) + mlp
                 + plan["sampled"] * 2 * d.d_model * d.vocab)


def kernel_work(kernel, d, config, plan):
    if kernel != "paged_chunk_attention":
        raise KeyError(f"no work count for kernel {kernel!r}")
    if plan["kind"] != "ragged":
        return None
    ctx = work.token_contexts(plan["slots"], plan["p_end"], plan["s_start"])
    slots = work.attended_slots(plan["row_of"], plan["slots"], plan["p_end"], plan["s_start"])
    kv = slots * (d.latent + d.rope) * config["kv_bytes"]
    qo = len(ctx) * d.heads * (d.latent + d.rope + d.latent) * config["act_bytes"]
    return float(_per_slot(d) * int(np.sum(ctx)) * d.layers), float((kv + qo) * d.layers)
