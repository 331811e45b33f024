"""jit'd wrapper for decode attention + the distributed (SP) combine.

``decode_attention`` — single-device dispatch: the jnp oracle unless the
caller asks for the Pallas kernel. The kernel is never picked on its own:
the TPU compiler refuses its ``(1, bk, 1, dh)`` KV block over a
``(B, S, Hk, dh)`` cache, which breaks the (8, 128) tiling rule, so it runs
only in interpret mode until its layout is repaired. ``decode_attention_sharded_body`` — the shard_map body for a KV
cache sharded along the sequence axis: each shard computes partial
(out·l, l, m) and the shards combine with a max/logsumexp reduction over the
mesh axis, which is exactly FlashDecoding's split-K reduction lifted to the
mesh level.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("block_k", "use_pallas", "interpret"))
def decode_attention(
    q: jnp.ndarray,  # (B, H, dh)
    k: jnp.ndarray,  # (B, S, Hk, dh)
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    block_k: int = 512,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    if use_pallas:
        return decode_attention_pallas(
            q, k, v, lengths, block_k=block_k, interpret=interpret
        )
    return decode_attention_ref(q, k, v, lengths)


def _partial_softmax_stats(q, k, v, valid_mask, scale):
    """One shard's contribution: returns (acc (B,H,dh), l (B,H,1), m (B,H,1))."""
    b, h, dh = q.shape
    _, s, hk, _ = k.shape
    g = h // hk
    qg = q.reshape(b, hk, g, dh).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg * scale, k.astype(jnp.float32))
    scores = jnp.where(valid_mask[:, None, None, :], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)  # (B,Hk,G,1)
    m_safe = jnp.maximum(m, -1e30)
    p = jnp.exp(scores - m_safe)
    p = jnp.where(valid_mask[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return acc.reshape(b, h, dh), l.reshape(b, h, 1), m_safe.reshape(b, h, 1)


def decode_attention_sharded_body(
    q: jnp.ndarray,  # (B, H, dh) — replicated over the seq-shard axis
    k_shard: jnp.ndarray,  # (B, S_local, Hk, dh)
    v_shard: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,) global lengths
    *,
    axis_name: str,
    scale: float | None = None,
) -> jnp.ndarray:
    """shard_map body: distributed flash-decode over ``axis_name``."""
    b, h, dh = q.shape
    s_local = k_shard.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(dh)
    shard = jax.lax.axis_index(axis_name)
    start = shard * s_local
    pos = start + jnp.arange(s_local)[None, :]
    valid = pos < lengths[:, None]
    acc, l, m = _partial_softmax_stats(q, k_shard, v_shard, valid, scale)
    # combine across shards: global max, rescale, sum
    m_glob = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_glob)
    acc = jax.lax.psum(acc * corr, axis_name)
    l = jax.lax.psum(l * corr, axis_name)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)
