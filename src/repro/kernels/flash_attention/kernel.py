"""Flash attention (causal, GQA) as a Pallas TPU kernel.

Adaptation of FlashAttention's IO-aware tiling to the TPU memory hierarchy:
Q/K/V stream HBM→VMEM in MXU-aligned blocks; the online-softmax state
(running max m, normalizer l, accumulator acc) lives in VMEM scratch and
persists across the innermost (sequential) KV-block grid dimension.

Grid: (B, H, n_q_blocks, n_kv_blocks) — the last dim is "arbitrary"
(sequential) so scratch carries across KV blocks; init at kv_idx == 0, final
normalize+store at the last kv block. Causal skipping: fully-masked KV
blocks (block start beyond the q block's last row) are no-ops via pl.when.

BlockSpecs (VMEM):
    q   (1, 1, bq, dh)   index (b, h, iq, ik) → (b, h, iq, 0)
    k/v (1, 1, bk, dh)   index (b, h, iq, ik) → (b, h // G, ik, 0)   [GQA]
    out (1, 1, bq, dh)   index (b, h, iq, ik) → (b, h, iq, 0)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, bq, bk, n_kv
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal block skip: with equal-length q/kv (prefill), kv block start
    # beyond q block end contributes nothing.
    q_start = iq * bq
    k_start = ik * bk
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _store():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, Sq, dh)
    k: jnp.ndarray,  # (B, Hk, Skv, dh)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, sq, dh = q.shape
    _, hk, skv, _ = k.shape
    if h % hk:
        raise ValueError(f"GQA requires H % Hk == 0, got {h} % {hk}")
    g = h // hk
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"seq lens ({sq},{skv}) must divide blocks ({bq},{bk})")
    n_q, n_kv = sq // bq, skv // bk
    if causal and sq != skv:
        raise ValueError("kernel causal path assumes Sq == Skv (prefill)")
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_kv=n_kv
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # m
            pltpu.VMEM((bq, 1), jnp.float32),  # l
            pltpu.VMEM((bq, dh), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
