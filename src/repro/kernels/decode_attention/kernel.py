"""Decode attention (flash-decoding) as a Pallas TPU kernel.

The decode_32k/long_500k hot path: one query token per sequence against a
long KV cache. FlashDecoding splits the KV sequence into blocks and combines
partial softmax results via the running (m, l) state — the same online-
softmax recurrence as prefill flash attention, but with a (G, dh) query tile
(all q-heads of one kv head) instead of a (bq, dh) tile, so the MXU matmul
is (G, dh) × (dh, bk).

Grid: (B, Hk, n_kv_blocks), last dim sequential ("arbitrary") with VMEM
scratch carrying (m, l, acc). Per-sequence valid length arrives as a
scalar-prefetch operand (SMEM) and masks the tail block.

This kernel is also the single-shard body of the *distributed* flash-decode:
under SP the cache's S axis shards over ``model`` and the per-shard (m, l,
acc) combine with one all-reduce (see distributed/partition.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,  # scalar-prefetch (B,) int32 in SMEM
    q_ref,  # (1, 1, G, dh)
    k_ref,  # (1, bk, 1, dh)
    v_ref,
    o_ref,  # (1, 1, G, dh)
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale,
    bk,
    n_kv,
):
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[ib]
    k_start = ik * bk
    # Skip blocks entirely beyond the valid prefix.
    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, dh)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bk)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _store():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _decode_kernel_q8(
    len_ref,  # scalar-prefetch (B,) int32
    q_ref,  # (1, 1, G, dh)
    k_ref,  # (1, bk, 1, dh) int8
    ks_ref,  # (1, bk, 1) f32 per-token-per-head scales
    v_ref,  # int8
    vs_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale,
    bk,
    n_kv,
):
    """int8-KV variant (KIVI-style): dequantize INSIDE the kernel so HBM
    traffic is the int8 payload + per-token scales (≈ 0.53× of bf16)."""
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[ib]
    k_start = ik * bk

    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, dh)
        k = k_ref[0, :, 0, :].astype(jnp.float32) * ks_ref[0, :, 0][:, None]
        v = v_ref[0, :, 0, :].astype(jnp.float32) * vs_ref[0, :, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _store():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def decode_attention_q8_pallas(
    q: jnp.ndarray,  # (B, H, dh)
    k_q: jnp.ndarray,  # (B, S, Hk, dh) int8
    k_scale: jnp.ndarray,  # (B, S, Hk) f32
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, dh = q.shape
    _, s, hk, _ = k_q.shape
    if h % hk:
        raise ValueError(f"GQA requires H % Hk == 0, got {h} % {hk}")
    g = h // hk
    bk = min(block_k, s)
    if s % bk:
        raise ValueError(f"cache len {s} must divide block_k {bk}")
    n_kv = s // bk
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    q4 = q.reshape(b, hk, g, dh)
    kernel = functools.partial(_decode_kernel_q8, scale=scale, bk=bk, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hk, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda ib, ih, ik, lens: (ib, ik, ih, 0)),
            pl.BlockSpec((1, bk, 1), lambda ib, ih, ik, lens: (ib, ik, ih)),
            pl.BlockSpec((1, bk, 1, dh), lambda ib, ih, ik, lens: (ib, ik, ih, 0)),
            pl.BlockSpec((1, bk, 1), lambda ib, ih, ik, lens: (ib, ik, ih)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention_q8",
    )(lengths.astype(jnp.int32), q4, k_q, k_scale, v_q, v_scale)
    return out.reshape(b, h, dh)


def quantize_kv(k: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token-per-head absmax int8 quantization of a KV tensor
    (B, S, Hk, dh) → (int8 same shape, f32 scales (B, S, Hk))."""
    absmax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1)
    scale = absmax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, dh)
    k: jnp.ndarray,  # (B, S, Hk, dh)
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,) int32
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, dh = q.shape
    _, s, hk, _ = k.shape
    if h % hk:
        raise ValueError(f"GQA requires H % Hk == 0, got {h} % {hk}")
    g = h // hk
    bk = min(block_k, s)
    if s % bk:
        raise ValueError(f"cache len {s} must divide block_k {bk}")
    n_kv = s // bk
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)

    q4 = q.reshape(b, hk, g, dh)
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hk, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda ib, ih, ik, lens: (ib, ik, ih, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda ib, ih, ik, lens: (ib, ik, ih, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), q4, k, v)
    return out.reshape(b, h, dh)
