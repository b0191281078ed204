"""Plain reference of a dense decoder with grouped-query attention (Qwen2,
Phi-3, Llama), and the seeded weights that both it and the served system run.

Layer equations, as the published architectures define them:

    h  = x + Wo · attn(rope(Wq·n1(x) + bq), rope(Wk·n1(x) + bk), Wv·n1(x) + bv)
    x' = h + Wd · (silu(Wg·n2(h)) * (Wu·n2(h)))
    n(x) = x / sqrt(mean(x²) + eps) · scale                    (RMSNorm)
    logits = nf(x) · Eᵀ  (tied embeddings)  or  nf(x) · W_head

RoPE in the rotate-half form with the config's ``rope_theta``; query head h
reads key/value head h // (H / KVH); attention scaled by 1/sqrt(head_dim).

Segmented RAG prompts follow the served system's stated semantics for
``[prelude][doc]...[doc][tail]``: a document token attends the prelude and
its own document up to itself, with positions restarting at the prelude's
end; every other token (prelude, tail, generated) attends everything before
it, position == index.

No kernels, no cache, no batching: float32 throughout, every product at
HIGHEST precision, run layer by layer and in blocks of query rows so that a
sequence of several thousand tokens fits beside the served weights.
``quant`` selects a control, the same forward one precision step below the
configuration's bfloat16: ``"int8"`` quantizes every weight per output
channel and every matmul input per row to int8 (symmetric absmax);
``"fp8"`` rounds them to float8 e4m3 (4 significant bits, per-tensor scale
to the format's largest value, 448).

The work counts that the per-layer metrics read for this architecture live
here too (``step_flops``, ``kernel_work``): model FLOPs of a served step
and each paged attention kernel's FLOPs and bytes, from the step's plan.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from bench.harness import work

Q_BLOCK = 256        # query rows per attention block
SEQ_BUCKET = 512     # sequences pad to a multiple of this: few compiled shapes


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    padded_vocab: int
    rope_theta: float
    eps: float
    tied: bool
    qkv_bias: bool

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """Dims from a published ``config.json`` (Hugging Face keys)."""
        heads = int(c["num_attention_heads"])
        d = int(c["hidden_size"])
        vocab = int(c["vocab_size"])
        return cls(
            layers=int(c["num_hidden_layers"]), d_model=d,
            d_ff=int(c["intermediate_size"]), heads=heads,
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or d // heads), vocab=vocab,
            padded_vocab=-(-vocab // 128) * 128,
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            tied=bool(c.get("tie_word_embeddings", False)),
            # Qwen2 attention carries q/k/v biases by architecture; others say
            # so in ``attention_bias``
            qkv_bias=bool(c.get("attention_bias", c.get("model_type") == "qwen2")))

    def param_count(self) -> int:
        d, L = self.d_model, self.layers
        attn = d * self.heads * self.head_dim * 2 + d * self.kv_heads * self.head_dim * 2
        if self.qkv_bias:
            attn += (self.heads + 2 * self.kv_heads) * self.head_dim
        per_layer = attn + 3 * d * self.d_ff + 2 * d
        emb = self.vocab * d * (1 if self.tied else 2)
        return L * per_layer + emb + d


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------


def linear_flops_per_token(d) -> float:
    """Projections and MLP of every layer, for one computed token."""
    qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_layer = 2 * (d.d_model * qd + 2 * d.d_model * kvd + qd * d.d_model
                     + 3 * d.d_model * d.d_ff)
    return float(per_layer * d.layers)


def logit_flops(d) -> float:
    """The output head for one sampled token."""
    return 2.0 * d.d_model * d.vocab


def attn_flops(d, ctx) -> float:
    """Scores and value sum of one layer for tokens with contexts ``ctx``."""
    return 4.0 * d.heads * d.head_dim * float(np.sum(ctx))


def chunk_kernel_work(d, kv_bytes: int, act_bytes: int, row_of, slots, p_end,
                      s_start) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``paged_chunk_attention`` over all layers for one
    packed step: each row's attended slots read once, every query read and
    every output written once."""
    flops = attn_flops(d, work.token_contexts(slots, p_end, s_start))
    kv_slots = work.attended_slots(row_of, slots, p_end, s_start)
    kv = kv_slots * d.kv_heads * d.head_dim * 2 * kv_bytes
    qo = len(slots) * d.heads * d.head_dim * 2 * act_bytes
    return flops * d.layers, float(kv + qo) * d.layers


def decode_kernel_work(d, kv_bytes: int, act_bytes: int, ctx) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``paged_decode_attention`` over all layers for one
    decode step whose rows attend ``ctx`` slots each."""
    ctx = np.asarray(ctx, np.int64)
    kv = float(ctx.sum()) * d.kv_heads * d.head_dim * 2 * kv_bytes
    qo = len(ctx) * d.heads * d.head_dim * 2 * act_bytes
    return attn_flops(d, ctx) * d.layers, (kv + qo) * d.layers


def step_flops(d, plan: Dict) -> Optional[float]:
    """Model FLOPs of one step: every computed token through every layer,
    its attention over its own context, and the head where a token is
    sampled. None for a kind of step this count does not know."""
    if plan["kind"] == "ragged":
        ctx = work.token_contexts(plan["slots"], plan["p_end"], plan["s_start"])
    elif plan["kind"] == "decode":
        ctx = np.asarray(plan["ctx"])
    else:
        return None
    return (len(ctx) * linear_flops_per_token(d) + attn_flops(d, ctx) * d.layers
            + plan["sampled"] * logit_flops(d))


def kernel_work(kernel: str, d, config: Dict, plan: Dict) -> Optional[Tuple[float, float]]:
    """(FLOPs, bytes) of ``kernel`` in one step, or None where the step does
    not run it. A kernel this architecture does not count is an error."""
    if kernel == "paged_chunk_attention":
        if plan["kind"] != "ragged":
            return None
        return chunk_kernel_work(d, config["kv_bytes"], config["act_bytes"], plan["row_of"],
                                 plan["slots"], plan["p_end"], plan["s_start"])
    if kernel == "paged_decode_attention":
        if plan["kind"] != "decode":
            return None
        return decode_kernel_work(d, config["kv_bytes"], config["act_bytes"], plan["ctx"])
    raise KeyError(f"no work count for kernel {kernel!r}")


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------


def _key(seed: int):
    import jax

    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def make_weights(dims: Dims, seed: int, dtype: str = "bfloat16",
                 shardings: Optional[Dict] = None) -> Dict:
    """Random weights from ``seed``, made on the device in one jitted call
    in the served dtype, placed as ``shardings`` says (by weight name) where
    given. Rows of the embedding (and head) past the published vocabulary
    are zero: they exist only because tables pad to 128 rows."""
    import jax

    make = functools.partial(_make_weights, dims, dtype)
    if shardings is not None:
        return jax.jit(make, out_shardings=shardings)(_key(seed))
    return jax.jit(make)(_key(seed))


def _make_weights(dims: Dims, dtype: str, key) -> Dict:
    import jax
    import jax.numpy as jnp

    L, d, f = dims.layers, dims.d_model, dims.d_ff
    qd, kvd = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    ks = iter(jax.random.split(key, 16))
    dt = jnp.dtype(dtype)

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dt)

    live = (jnp.arange(dims.padded_vocab) < dims.vocab)[:, None]
    w = {
        "embed": jnp.where(live, normal((dims.padded_vocab, d), 0.02), 0).astype(dt),
        "norm1": (1.0 + normal((L, d), 0.1).astype(jnp.float32)).astype(dt),
        "wq": normal((L, d, qd), d ** -0.5),
        "wk": normal((L, d, kvd), d ** -0.5),
        "wv": normal((L, d, kvd), d ** -0.5),
        "wo": normal((L, qd, d), qd ** -0.5),
        "norm2": (1.0 + normal((L, d), 0.1).astype(jnp.float32)).astype(dt),
        "w_gate": normal((L, d, f), d ** -0.5),
        "w_up": normal((L, d, f), d ** -0.5),
        "w_down": normal((L, f, d), f ** -0.5),
        "final_norm": (1.0 + normal((d,), 0.1).astype(jnp.float32)).astype(dt),
    }
    if dims.qkv_bias:
        w["bq"] = normal((L, qd), 0.1)
        w["bk"] = normal((L, kvd), 0.1)
        w["bv"] = normal((L, kvd), 0.1)
    if not dims.tied:
        head = normal((d, dims.padded_vocab), d ** -0.5)
        w["lm_head"] = jnp.where(live.T, head, 0).astype(dt)
    return w


# ---------------------------------------------------------------------------
# segmented prompt layout
# ---------------------------------------------------------------------------


def segment_layout(kinds: Sequence[str], lengths: Sequence[int]
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(positions, p_end, s_start) per token of a prompt made of segments of
    the given kinds ("doc" or anything else) and lengths. Token t may attend
    slot j iff ``j < p_end[t]`` or ``s_start[t] <= j <= t``."""
    n = int(sum(lengths))
    pos = np.arange(n, dtype=np.int32)
    p_end = np.zeros(n, np.int32)
    s_start = np.zeros(n, np.int32)
    starts = np.concatenate([[0], np.cumsum(lengths)]).astype(int)
    docs = [i for i, k in enumerate(kinds) if k == "doc"]
    prelude_end = starts[docs[0]] if docs else n
    for i in docs:
        a, b = starts[i], starts[i + 1]
        pos[a:b] = prelude_end + np.arange(b - a)
        p_end[a:b] = prelude_end
        s_start[a:b] = a
    return pos, p_end, s_start


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fq(x, axis):
    """Symmetric int8 fake quantization along ``axis`` (absmax / 127)."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _f8(x, axis=None):
    """Float8 e4m3 rounding: scale so the largest magnitude is 448, keep 4
    significant bits, scale back (subnormals round like normals)."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    m, e = jnp.frexp(x / s)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) * s


def _q(x, axis, quant):
    if quant == "int8":
        return _fq(x, axis)
    if quant == "fp8":
        return _f8(x)
    return x


def _mm(a, b, quant):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(_q(a, -1, quant), _q(b, 0, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    import jax.numpy as jnp

    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(dims: Dims, quant: Optional[str], w, l, x, pos, p_end, s_start):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def get(name):
        return jax.lax.dynamic_index_in_dim(w[name], l, keepdims=False).astype(f32)

    S = x.shape[0]
    H, KVH, hd = dims.heads, dims.kv_heads, dims.head_dim
    h = _rms(x, get("norm1"), dims.eps)
    q, k, v = _mm(h, get("wq"), quant), _mm(h, get("wk"), quant), _mm(h, get("wv"), quant)
    if dims.qkv_bias:
        q, k, v = q + get("bq"), k + get("bk"), v + get("bv")
    q = _rope(q.reshape(S, H, hd), pos, dims.rope_theta)
    k = _rope(k.reshape(S, KVH, hd), pos, dims.rope_theta)
    v = v.reshape(S, KVH, hd)
    q, k, v = _q(q, -1, quant), _q(k, -1, quant), _q(v, -1, quant)
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    slots = jnp.arange(S)
    scale = 1.0 / math.sqrt(hd)
    nb = S // Q_BLOCK

    def block(i):
        lo = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, lo, Q_BLOCK)            # (bq, H, hd)
        rows = lo + jnp.arange(Q_BLOCK)
        pe = jax.lax.dynamic_slice_in_dim(p_end, lo, Q_BLOCK)
        ss = jax.lax.dynamic_slice_in_dim(s_start, lo, Q_BLOCK)
        ok = (slots[None] < pe[:, None]) | (
            (slots[None] >= ss[:, None]) & (slots[None] <= rows[:, None]))
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        s = jnp.where(ok[None], s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, quant)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    attn = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * hd)
    x = x + _mm(attn, get("wo"), quant)
    h = _rms(x, get("norm2"), dims.eps)
    ff = jax.nn.silu(_mm(h, get("w_gate"), quant)) * _mm(h, get("w_up"), quant)
    return x + _mm(ff, get("w_down"), quant)


def _embed(w, tokens):
    import jax.numpy as jnp

    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)


def _head(dims: Dims, quant: Optional[str], w, x):
    import jax.numpy as jnp

    x = _rms(x, w["final_norm"], dims.eps)
    table = (w["embed"].T if dims.tied else w["lm_head"])[:, : dims.vocab]
    return _mm(x, table.astype(jnp.float32), quant)


@functools.lru_cache(maxsize=None)
def _programs(dims: Dims, quant: Optional[str]):
    import jax

    return (jax.jit(_embed),
            jax.jit(functools.partial(_layer, dims, quant)),
            jax.jit(functools.partial(_head, dims, quant)))


def forward_logits(w: Dict, dims: Dims, tokens, positions, p_end, s_start,
                   rows: Sequence[int], quant: Optional[str] = None) -> np.ndarray:
    """Logits (len(rows), vocab) float32 of the sequence ``tokens`` (with its
    layout arrays) at the given row indices. ``quant`` ("int8", "fp8") runs
    a control instead of the reference."""
    import jax.numpy as jnp

    if quant not in (None, "int8", "fp8"):
        raise ValueError(f"unknown quant {quant!r}")
    S = len(tokens)
    Sp = -(-S // SEQ_BUCKET) * SEQ_BUCKET

    def pad(a):
        out = np.zeros(Sp, np.int32)
        out[:S] = a
        return jnp.asarray(out)

    # pad tokens sit after every real token, so causal spans never reach them
    embed, layer, head = _programs(dims, quant)
    x = embed(w, pad(tokens))
    pos, pe, ss = pad(positions), pad(p_end), pad(s_start)
    for l in range(dims.layers):
        x = layer(w, jnp.int32(l), x, pos, pe, ss)
    n = len(rows)
    idx = np.zeros(-(-n // 128) * 128, np.int32)   # few compiled head shapes
    idx[:n] = rows
    return np.asarray(head(w, x[jnp.asarray(idx)]), np.float32)[:n]
