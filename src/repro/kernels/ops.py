"""Jit'd public wrappers for the Pallas kernels.

Every wrapper takes ``default_interpret()``: compiled Mosaic on a TPU, the
Pallas interpreter on any other platform.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.decode_attention import default_interpret
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rwkv6_scan import rwkv6_chunked as _rwkv6
from repro.kernels.ssm_scan import ssm_scan as _ssm
from repro.kernels.topk_retrieval import topk_retrieval as _topk


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 256, block_k: int = 256):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=default_interpret())


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, lengths, block_k: int = 512):
    return _decode(q, k_cache, v_cache, lengths, block_k=block_k, interpret=default_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_chunked(r, k, v, w, u, state0=None, chunk: int = 32):
    return _rwkv6(r, k, v, w, u, state0, chunk=chunk, interpret=default_interpret())


@partial(jax.jit, static_argnames=("k", "block_n"))
def topk_retrieval(queries, docs, k: int = 16, block_n: int = 1024):
    return _topk(queries, docs, k=k, block_n=block_n, interpret=default_interpret())


@partial(jax.jit, static_argnames=("chunk", "di_block"))
def ssm_scan(dt, x, bm, cm, a_log, chunk: int = 32, di_block: int = 256):
    return _ssm(dt, x, bm, cm, a_log, chunk=chunk, di_block=di_block,
                interpret=default_interpret())
