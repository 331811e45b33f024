"""Decode-attention kernel vs oracle + distributed (SP) combine equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import make_mesh
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ops import (
    decode_attention,
    decode_attention_sharded_body,
)
from repro.kernels.decode_attention.ref import decode_attention_ref


def _inputs(b, h, hk, s, dh, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, h, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hk, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hk, dh)).astype(dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    return q, k, v, lengths


SWEEP = [
    # (b, h, hk, s, dh, bk, dtype, rtol)
    (2, 4, 2, 256, 64, 128, jnp.float32, 2e-5),
    (1, 8, 8, 512, 64, 128, jnp.float32, 2e-5),  # MHA
    (3, 6, 2, 384, 32, 128, jnp.float32, 2e-5),  # group 3
    (2, 4, 1, 256, 128, 64, jnp.bfloat16, 2e-2),  # MQA bf16
]


@pytest.mark.parametrize("b,h,hk,s,dh,bk,dtype,rtol", SWEEP)
def test_decode_kernel_matches_ref(b, h, hk, s, dh, bk, dtype, rtol):
    q, k, v, lengths = _inputs(b, h, hk, s, dh, dtype)
    out = decode_attention_pallas(q, k, v, lengths, block_k=bk, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=rtol, atol=rtol
    )


def test_decode_length_masking_strict():
    """Garbage beyond `lengths` must not leak into the output."""
    q, k, v, _ = _inputs(2, 4, 2, 256, 64, jnp.float32, seed=1)
    lengths = jnp.array([100, 200])
    out1 = decode_attention_pallas(q, k, v, lengths, block_k=64, interpret=True)
    k2 = k.at[0, 100:].set(1e4)
    v2 = v.at[0, 100:].set(-1e4)
    out2 = decode_attention_pallas(q, k2, v2, lengths, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_decode_matches_full_prefix_softmax():
    """lengths == S reduces to plain cross-attention of 1 token."""
    from repro.models.layers import gqa_attention

    b, h, hk, s, dh = 2, 4, 2, 128, 64
    q, k, v, _ = _inputs(b, h, hk, s, dh, jnp.float32, seed=2)
    lengths = jnp.full((b,), s)
    out = decode_attention_pallas(q, k, v, lengths, block_k=64, interpret=True)
    ref = gqa_attention(q[:, None].reshape(b, 1, h, dh), k, v, causal=False)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_decode_wrapper_dispatches_oracle_on_cpu():
    q, k, v, lengths = _inputs(1, 2, 2, 128, 32, jnp.float32)
    out = decode_attention(q, k, v, lengths)  # CPU → oracle path
    ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_decode_invalid_shapes():
    q, k, v, lengths = _inputs(1, 3, 2, 128, 32, jnp.float32)
    with pytest.raises(ValueError):
        decode_attention_pallas(q, k, v, lengths, interpret=True)
    q, k, v, lengths = _inputs(1, 2, 2, 100, 32, jnp.float32)
    with pytest.raises(ValueError):
        decode_attention_pallas(q, k, v, lengths, block_k=64, interpret=True)


def test_distributed_flash_decode_matches_single_device():
    """SP combine (shard_map over seq axis) == oracle, incl. partial lengths."""
    from jax.sharding import PartitionSpec as P

    b, h, hk, s, dh = 2, 4, 2, 256, 32
    q, k, v, lengths = _inputs(b, h, hk, s, dh, jnp.float32, seed=3)
    mesh = make_mesh((1,), ("model",))
    body = lambda q, k, v, lens: decode_attention_sharded_body(
        q, k, v, lens, axis_name="model"
    )
    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(None, "model", None, None), P(None, "model", None, None), P()),
            out_specs=P(),
            check_vma=False,
        )
    )
    out = fn(q, k, v, lengths)
    ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_sharded_body_zero_length_sequence():
    """A sequence with length 0 must produce zeros, not NaNs."""
    b, h, hk, s, dh = 2, 2, 2, 64, 16
    q, k, v, _ = _inputs(b, h, hk, s, dh, jnp.float32, seed=4)
    lengths = jnp.array([0, 32])
    out = decode_attention_pallas(q, k, v, lengths, block_k=32, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-6)


# --------------------------------------------------------------------------- #
# int8 KV-cache variant (KIVI-style dequant-in-kernel)                          #
# --------------------------------------------------------------------------- #
def test_q8_kernel_matches_f32_within_quant_error():
    from repro.kernels.decode_attention.kernel import decode_attention_q8_pallas, quantize_kv

    q, k, v, lengths = _inputs(2, 4, 2, 256, 64, jnp.float32, seed=5)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    out_q8 = decode_attention_q8_pallas(q, kq, ks, vq, vs, lengths, block_k=64, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    # int8 per-token-per-head quantization: ~1% relative error budget
    np.testing.assert_allclose(np.asarray(out_q8), np.asarray(ref), rtol=0.05, atol=0.05)


def test_q8_kernel_matches_dequantized_ref_exactly():
    """vs the oracle computed on the dequantized cache (isolates kernel logic)."""
    from repro.kernels.decode_attention.kernel import decode_attention_q8_pallas, quantize_kv

    q, k, v, lengths = _inputs(2, 4, 4, 128, 32, jnp.float32, seed=6)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    k_deq = kq.astype(jnp.float32) * ks[..., None]
    v_deq = vq.astype(jnp.float32) * vs[..., None]
    out_q8 = decode_attention_q8_pallas(q, kq, ks, vq, vs, lengths, block_k=32, interpret=True)
    ref = decode_attention_ref(q, k_deq, v_deq, lengths)
    np.testing.assert_allclose(np.asarray(out_q8), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_quantize_kv_roundtrip_error_bounded():
    from repro.kernels.decode_attention.kernel import quantize_kv

    k = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 32)) * 3.0
    kq, ks = quantize_kv(k)
    back = kq.astype(jnp.float32) * ks[..., None]
    err = np.abs(np.asarray(back - k))
    bound = np.asarray(ks)[..., None] / 2 + 1e-6
    assert (err <= bound).all()
    assert kq.dtype == jnp.int8
