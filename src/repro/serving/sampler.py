"""Token sampling for the generation engine."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_tokens(key, logits, temperature=0.0, top_k: int = 0):
    """logits: (B, V) -> (B,) int32.

    ``temperature`` is either a python scalar (shared by the whole batch) or a
    (B,) array of per-request temperatures — continuous batching mixes greedy
    and sampled requests in one decode step, and each row must be sampled
    under its own temperature. Rows with temperature <= 0 decode greedily.
    """
    with jax.named_scope("sample"):
        t = jnp.asarray(temperature, jnp.float32)
        if t.ndim == 0:
            if float(t) <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = logits / t
            if top_k:
                vals, _ = jax.lax.top_k(logits, top_k)
                cutoff = vals[:, -1:]
                logits = jnp.where(logits >= cutoff, logits, -1e30)
            return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(t, 1e-6)[:, None]
        if top_k:
            vals, _ = jax.lax.top_k(scaled, top_k)
            scaled = jnp.where(scaled >= vals[:, -1:], scaled, -1e30)
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
        return jnp.where(t > 0.0, sampled, greedy)
