"""Training substrate tests: optimizer, compression, train loop, checkpoint,
fault tolerance, data pipeline."""

import os

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import (
    AdamWConfig,
    CheckpointManager,
    HeartbeatMonitor,
    Int8Compressor,
    LMDataConfig,
    Prefetcher,
    RestartSupervisor,
    StragglerDetector,
    TokenStream,
    TopKCompressor,
    TrainingFailure,
    TrainStepConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    global_norm,
    make_adamw,
    make_sgd,
    make_train_step,
    microbatch,
    pack_documents,
    warmup_cosine,
)
from repro.training.optimizer import dequantize_blockwise, quantize_blockwise


# --------------------------------------------------------------------------- #
# Quantization                                                                 #
# --------------------------------------------------------------------------- #
def test_blockwise_quant_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000, 37)) * 3.0
    q = quantize_blockwise(x)
    back = dequantize_blockwise(q, x.shape)
    # per-block max error <= scale/2 ⇒ relative to block absmax <= 1/254
    err = np.abs(np.asarray(back - x))
    assert err.max() <= float(jnp.abs(x).max()) / 127.0 + 1e-6
    assert q.q.dtype == jnp.int8


def test_quant_zero_tensor():
    x = jnp.zeros((100,))
    back = dequantize_blockwise(quantize_blockwise(x), x.shape)
    np.testing.assert_allclose(np.asarray(back), 0.0)


@hypothesis.given(st.integers(min_value=1, max_value=5000))
@hypothesis.settings(max_examples=20, deadline=None)
def test_quant_shapes_property(n):
    x = jnp.asarray(np.random.default_rng(n).normal(size=(n,)).astype(np.float32))
    back = dequantize_blockwise(quantize_blockwise(x), x.shape)
    assert back.shape == x.shape


# --------------------------------------------------------------------------- #
# AdamW                                                                        #
# --------------------------------------------------------------------------- #
def _quadratic_params():
    return {"w": jnp.array([3.0, -2.0, 1.0]), "b": jnp.array([[0.5, -0.5]])}


def _quadratic_loss(p):
    return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)


def test_adamw_converges_on_quadratic():
    params = _quadratic_params()
    cfg = AdamWConfig(lr=0.1, max_grad_norm=None)
    state = adamw_init(params, cfg)
    for _ in range(200):
        grads = jax.grad(_quadratic_loss)(params)
        params, state, m = adamw_update(grads, state, params, cfg)
    assert float(_quadratic_loss(params)) < 1e-3


def test_adamw_int8_moments_converge():
    params = _quadratic_params()
    cfg = AdamWConfig(lr=0.1, max_grad_norm=None, moment_dtype="int8")
    state = adamw_init(params, cfg)
    for _ in range(200):
        grads = jax.grad(_quadratic_loss)(params)
        params, state, _ = adamw_update(grads, state, params, cfg)
    assert float(_quadratic_loss(params)) < 5e-3
    # moments actually stored int8
    assert jax.tree.leaves(state["m"], is_leaf=lambda x: hasattr(x, "q"))[0].q.dtype == jnp.int8


def test_adamw_weight_decay_shrinks():
    params = {"w": jnp.array([10.0])}
    cfg = AdamWConfig(lr=0.01, weight_decay=0.1, max_grad_norm=None)
    state = adamw_init(params, cfg)
    zero_grads = {"w": jnp.array([0.0])}
    for _ in range(50):
        params, state, _ = adamw_update(zero_grads, state, params, cfg)
    assert float(params["w"][0]) < 10.0


def test_grad_clip_by_global_norm():
    tree = {"a": jnp.array([3.0, 4.0])}  # norm 5
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    not_clipped, _ = clip_by_global_norm(tree, 10.0)
    np.testing.assert_allclose(np.asarray(not_clipped["a"]), [3.0, 4.0])


def test_warmup_cosine_schedule_shape():
    lr = warmup_cosine(1.0, warmup_steps=10, total_steps=100, min_ratio=0.1)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1.0)
    assert float(lr(5)) == pytest.approx(0.5)
    assert float(lr(100)) == pytest.approx(0.1, abs=1e-6)
    assert float(lr(55)) > float(lr(90))


def test_sgd_momentum_converges():
    params = _quadratic_params()
    opt = make_sgd()
    state = opt.init(params)
    for _ in range(100):
        grads = jax.grad(_quadratic_loss)(params)
        params, state, _ = opt.update(grads, state, params)
    assert float(_quadratic_loss(params)) < 1e-3


# --------------------------------------------------------------------------- #
# Compression                                                                  #
# --------------------------------------------------------------------------- #
def test_int8_compressor_error_feedback_unbiased_longrun():
    """EF ⇒ compressed-SGD trajectory tracks uncompressed on a quadratic."""
    comp = Int8Compressor()
    params = {"w": jnp.array([5.0, -3.0])}
    residual = comp.init_residual(params)
    lr = 0.05
    for _ in range(300):
        grads = jax.grad(_quadratic_loss_w)(params)
        payload, residual = comp.compress(grads, residual)
        deq = comp.decompress(payload, grads)
        params = jax.tree.map(lambda p, g: p - lr * g, params, deq)
    assert float(_quadratic_loss_w(params)) < 1e-4


def _quadratic_loss_w(p):
    return jnp.sum(p["w"] ** 2)


def test_topk_compressor_sparsity_and_ef():
    comp = TopKCompressor(fraction=0.1)
    params = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(100,)).astype(np.float32))}
    residual = comp.init_residual(params)
    grads = jax.grad(_quadratic_loss_w)(params)
    payload, residual = comp.compress(grads, residual)
    leaf = jax.tree.leaves(payload, is_leaf=lambda x: hasattr(x, "indices"))[0]
    assert leaf.values.shape == (10,)
    deq = comp.decompress(payload)
    # decompressed has exactly k nonzeros
    assert int((np.asarray(deq["w"]) != 0).sum()) == 10
    # residual holds the complement: deq + residual == grads (+0 prior residual)
    np.testing.assert_allclose(
        np.asarray(deq["w"] + residual["w"]), np.asarray(grads["w"]), rtol=1e-6
    )


def test_topk_compressed_sgd_converges():
    comp = TopKCompressor(fraction=0.2)
    params = {"w": jnp.asarray(np.linspace(-2, 2, 50).astype(np.float32))}
    residual = comp.init_residual(params)
    for _ in range(400):
        grads = jax.grad(_quadratic_loss_w)(params)
        payload, residual = comp.compress(grads, residual)
        deq = comp.decompress(payload)
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, deq)
    assert float(_quadratic_loss_w(params)) < 1e-3


def test_compressor_bytes_ratios():
    assert Int8Compressor().bytes_ratio() < 0.3
    assert TopKCompressor(fraction=0.01).bytes_ratio() == pytest.approx(0.02)


# --------------------------------------------------------------------------- #
# Train loop                                                                   #
# --------------------------------------------------------------------------- #
def _toy_loss(params, batch):
    pred = batch["x"] @ params["w"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"mse": loss}


def _toy_batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    w_true = np.array([1.5, -2.0])
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=n).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def test_train_step_learns_regression():
    params = {"w": jnp.zeros((2,))}
    opt = make_adamw(AdamWConfig(lr=0.05, max_grad_norm=None))
    step = jax.jit(make_train_step(_toy_loss, opt))
    state = opt.init(params)
    batch = _toy_batch()
    for _ in range(300):
        params, state, metrics = step(params, state, batch)
    assert float(metrics["loss"]) < 1e-3
    np.testing.assert_allclose(np.asarray(params["w"]), [1.5, -2.0], atol=0.05)


def test_grad_accumulation_matches_full_batch():
    params = {"w": jnp.array([0.3, -0.7])}
    opt = make_adamw(AdamWConfig(lr=0.01, max_grad_norm=None))
    batch = _toy_batch(n=32)
    step1 = make_train_step(_toy_loss, opt, TrainStepConfig(n_microbatches=1))
    step4 = make_train_step(_toy_loss, opt, TrainStepConfig(n_microbatches=4))
    p1, s1, m1 = step1(params, opt.init(params), batch)
    p4, s4, m4 = step4(params, opt.init(params), batch)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p4["w"]), rtol=1e-5)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-5)


def test_microbatch_validates_divisibility():
    with pytest.raises(ValueError):
        microbatch({"x": jnp.zeros((10, 3))}, 3)


def test_train_step_with_compression_runs():
    params = {"w": jnp.zeros((2,))}
    opt = make_adamw(AdamWConfig(lr=0.05, max_grad_norm=None))
    comp = Int8Compressor()
    step = make_train_step(_toy_loss, opt, TrainStepConfig(compressor=comp))
    state = opt.init(params)
    residual = comp.init_residual(params)
    batch = _toy_batch()
    for _ in range(200):
        params, state, residual, metrics = step(params, state, batch, residual)
    assert float(metrics["loss"]) < 5e-3


# --------------------------------------------------------------------------- #
# Checkpointing                                                                #
# --------------------------------------------------------------------------- #
def _ckpt_tree(x=1.0):
    return {"params": {"w": jnp.full((4, 3), x)}, "opt": {"step": jnp.array(7, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _ckpt_tree(2.5)
    mgr.save(3, tree, metadata={"note": "hi"})
    restored, manifest = mgr.restore(jax.tree.map(lambda x: jnp.zeros_like(x), tree))
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 2.5)
    assert int(restored["opt"]["step"]) == 7
    assert manifest["metadata"]["note"] == "hi"
    assert mgr.latest_step() == 3


def test_checkpoint_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _ckpt_tree(float(s)))
    assert mgr.available_steps() == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    h = mgr.save_async(5, _ckpt_tree(1.0))
    h.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_incomplete_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _ckpt_tree())
    # fabricate an incomplete dir (no _COMPLETE marker)
    os.makedirs(tmp_path / "step_0000000002")
    assert mgr.latest_step() == 1
    with pytest.raises(FileNotFoundError):
        mgr.restore(_ckpt_tree(), step=2)


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _ckpt_tree())
    bad_like = {"params": {"w": jnp.zeros((2, 2))}, "opt": {"step": jnp.array(0, jnp.int32)}}
    with pytest.raises(ValueError):
        mgr.restore(bad_like)


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore with an explicit sharding_fn placing leaves on a new mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import make_mesh

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _ckpt_tree(3.0))
    mesh = make_mesh((1,), ("data",))

    def sharding_fn(path, leaf):
        return NamedSharding(mesh, P())

    restored, _ = mgr.restore(_ckpt_tree(0.0), sharding_fn=sharding_fn)
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 3.0)


# --------------------------------------------------------------------------- #
# Fault tolerance                                                              #
# --------------------------------------------------------------------------- #
def test_restart_supervisor_recovers(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    sup = RestartSupervisor(mgr, checkpoint_every=5, max_restarts=3)
    fail_at = {12}  # one injected failure after step 12

    def init_fn():
        return {"x": jnp.array(0.0)}

    def step_fn(state, step):
        if step in fail_at:
            fail_at.clear()
            raise TrainingFailure("injected")
        return {"x": state["x"] + 1.0}

    state, report = sup.run(init_fn, step_fn, total_steps=20)
    assert report.restarts == 1
    assert report.completed_steps == 20
    # restored from step 10 (latest checkpoint before the failure)
    assert report.restored_from == [10]
    assert float(state["x"]) == 20.0  # replayed steps included


def test_restart_supervisor_budget_exhausted(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    sup = RestartSupervisor(mgr, checkpoint_every=100, max_restarts=1)

    def step_fn(state, step):
        raise TrainingFailure("always")

    with pytest.raises(TrainingFailure):
        sup.run(lambda: {"x": jnp.array(0.0)}, step_fn, total_steps=5)


def test_heartbeat_monitor():
    t = {"now": 0.0}
    mon = HeartbeatMonitor(["a", "b"], timeout_s=10, clock=lambda: t["now"])
    t["now"] = 5.0
    mon.beat("a")
    t["now"] = 12.0
    assert mon.dead_workers() == ["b"]
    assert not mon.all_alive()


def test_straggler_detector():
    det = StragglerDetector(["w0", "w1", "w2", "w3"], threshold=1.5)
    for _ in range(5):
        det.record("w0", 1.0)
        det.record("w1", 1.1)
        det.record("w2", 0.9)
        det.record("w3", 3.0)  # straggler
    assert det.stragglers() == ["w3"]
    assert det.mitigation_plan()["action"] == "reassign"


def test_straggler_detector_needs_samples():
    det = StragglerDetector(["a", "b"], min_samples=3)
    det.record("a", 1.0)
    det.record("b", 99.0)
    assert det.stragglers() == []


# --------------------------------------------------------------------------- #
# Data pipeline                                                                #
# --------------------------------------------------------------------------- #
def test_token_stream_deterministic_and_sharded():
    cfg = LMDataConfig(vocab=100, seq_len=16, batch=4, seed=42)
    b1 = next(TokenStream(cfg, 0, 2).batches())
    b2 = next(TokenStream(cfg, 0, 2).batches())
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b_other = next(TokenStream(cfg, 1, 2).batches())
    assert not np.array_equal(b1["tokens"], b_other["tokens"])
    # next-token alignment
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])


def test_pack_documents():
    docs = [np.arange(1, 6), np.arange(10, 13), np.arange(20, 30)]
    packed = pack_documents(docs, seq_len=8, pad_id=0)
    assert packed.shape[1] == 8
    flat = packed.reshape(-1)
    nonpad = flat[flat != 0]
    np.testing.assert_array_equal(
        nonpad, np.concatenate([np.arange(1, 6), np.arange(10, 13), np.arange(20, 30)])
    )


def test_prefetcher_yields_all():
    it = iter([{"i": i} for i in range(7)])
    out = [b["i"] for b in Prefetcher(it, depth=2)]
    assert out == list(range(7))
