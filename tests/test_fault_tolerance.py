"""Fault-tolerance substrate: checkpoint atomicity + elastic resharding,
resumable deterministic data, gradient compression convergence."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_for_smoke
from repro.data.pipeline import LMStreamConfig, PrefetchIterator, SyntheticLM, SyntheticVWW
from repro.launch.mesh import make_host_mesh
from repro.models.transformer import init_model
from repro.training.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.training.compression import compress_decompress, init_error_state
from repro.training.optimizer import AdamWConfig, adamw_update, init_adamw


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def _state():
    cfg = reduce_for_smoke(ARCHS["qwen3-1.7b"])
    params = init_model(jax.random.PRNGKey(0), cfg)
    return params, init_adamw(params)


def test_checkpoint_roundtrip(tmp_path):
    params, opt = _state()
    save_checkpoint(tmp_path, 7, (params, opt), extra={"cursor": 7})
    (p2, o2), extra = restore_checkpoint(tmp_path, (params, opt))
    assert extra["step"] == 7 and extra["cursor"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(o2.step) == int(opt.step)


def test_checkpoint_retention_and_latest(tmp_path):
    params, opt = _state()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, s, (params, opt), keep=3)
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4, 5]
    assert latest_step(tmp_path) == 5


def test_checkpoint_atomicity(tmp_path):
    """A leftover .tmp dir (simulated crash) must not shadow a good ckpt."""
    params, opt = _state()
    save_checkpoint(tmp_path, 1, (params, opt))
    crash = tmp_path / "step_00000002.tmp"
    crash.mkdir()
    (crash / "garbage").write_text("boom")
    assert latest_step(tmp_path) == 1
    restore_checkpoint(tmp_path, (params, opt))  # must not raise


def test_checkpoint_detects_structure_mismatch(tmp_path):
    params, opt = _state()
    save_checkpoint(tmp_path, 1, params)
    other = init_model(jax.random.PRNGKey(0), reduce_for_smoke(ARCHS["yi-9b"]))
    with pytest.raises(ValueError):
        restore_checkpoint(tmp_path, other)


def test_elastic_resharding(tmp_path):
    """Save unsharded, restore onto a 1x1 mesh sharding (the elastic path);
    values must be identical."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    params, _ = _state()
    save_checkpoint(tmp_path, 3, params)
    mesh = make_host_mesh(1, 1)
    shardings = jax.tree.map(
        lambda p: NamedSharding(mesh, P(*([None] * p.ndim))), params
    )
    restored, _ = restore_checkpoint(tmp_path, params, shardings=shardings)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding.mesh.shape == {"data": 1, "model": 1}


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_deterministic_and_resumable():
    cfg = LMStreamConfig(vocab_size=97, seq_len=32, global_batch=8, seed=3)
    s1, s2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1 = s1.batch_at(41)
    b2 = s2.batch_at(41)  # fresh object, same address -> same bytes
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (8, 32)
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_data_sharding_partitions_batch():
    cfg = LMStreamConfig(vocab_size=97, seq_len=16, global_batch=8, seed=0)
    s = SyntheticLM(cfg)
    shards = [s.batch_at(5, shard=i, n_shards=4)["tokens"] for i in range(4)]
    assert all(x.shape == (2, 16) for x in shards)
    flat = np.concatenate([x.ravel() for x in shards])
    assert len(np.unique(flat)) > 1  # shards differ
    a = s.batch_at(5, shard=0, n_shards=4)["tokens"]
    np.testing.assert_array_equal(a, shards[0])  # per-shard determinism


def test_data_is_learnable():
    """The affine-recurrence stream must be predictable from the previous
    token (else the end-to-end training example can't show loss decrease)."""
    cfg = LMStreamConfig(vocab_size=50, seq_len=64, global_batch=4, seed=1, noise=0.0)
    b = SyntheticLM(cfg).batch_at(0)
    t, l = b["tokens"], b["labels"]
    # next token is a fixed function of current: same current => same next
    pairs = {}
    for cur, nxt in zip(t.ravel(), l.ravel()):
        assert pairs.setdefault(int(cur), int(nxt)) == int(nxt)


def test_prefetch_and_stall_detection():
    calls = []

    def make(step):
        calls.append(step)
        return {"x": step}

    it = PrefetchIterator(make, start_step=10, timeout_s=5.0)
    s, b = next(it)
    assert s == 10 and b["x"] == 10
    s, b = next(it)
    assert s == 11
    it.close()

    slow = PrefetchIterator(lambda s: (__import__("time").sleep(10), s)[1], timeout_s=0.2)
    with pytest.raises(TimeoutError):
        next(slow)
    assert slow.stalls == 1
    slow.close()


def test_vww_is_shape_coded_not_brightness_coded():
    data = SyntheticVWW((48, 48))
    b = data.batch_at(0, 256)
    imgs, labels = b["images"], b["labels"]
    # class means differ structurally...
    mean_pos = imgs[labels == 1].mean(axis=0)
    mean_neg = imgs[labels == 0].mean(axis=0)
    assert np.abs(mean_pos - mean_neg).max() > 0.02
    # ...but a max-brightness threshold cannot separate (no intensity shortcut)
    bright = imgs.reshape(len(imgs), -1).max(axis=1)
    best_acc = 0.0
    for thr in np.linspace(bright.min(), bright.max(), 64):
        acc = max(
            ((bright > thr) == labels).mean(), ((bright <= thr) == labels).mean()
        )
        best_acc = max(best_acc, acc)
    assert best_acc < 0.75


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_compression_error_feedback_is_unbiased_over_time():
    """Error feedback: the *sum* of compressed grads tracks the sum of true
    grads (residual stays bounded), so optimisation converges."""
    rng = np.random.default_rng(0)
    g_sum = np.zeros((64,), np.float32)
    ghat_sum = np.zeros((64,), np.float32)
    err = {"w": jnp.zeros((64,), jnp.float32)}
    for _ in range(50):
        g = {"w": jnp.asarray(rng.normal(0, 1, 64), jnp.float32)}
        ghat, err, _ = compress_decompress(g, err)
        g_sum += np.asarray(g["w"])
        ghat_sum += np.asarray(ghat["w"])
    resid = np.abs(g_sum - ghat_sum).max()
    assert resid < 0.1  # bounded by one quantisation step, not 50 of them


def test_compressed_training_converges():
    """Linear regression with int8+EF grads reaches the uncompressed loss."""
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(0, 1, (256, 16)), jnp.float32)
    w_true = jnp.asarray(rng.normal(0, 1, (16,)), jnp.float32)
    y = X @ w_true

    def loss_fn(params):
        return jnp.mean((X @ params["w"] - y) ** 2)

    grad_fn = jax.jit(jax.grad(loss_fn))
    cfg = AdamWConfig(lr=3e-2, weight_decay=0.0, warmup_steps=1, total_steps=400)

    def run(compressed: bool):
        params = {"w": jnp.zeros((16,), jnp.float32)}
        opt = init_adamw(params)
        err = init_error_state(params)
        for _ in range(400):
            g = grad_fn(params)
            if compressed:
                g, err, _ = compress_decompress(g, err)
            params, opt, _ = adamw_update(g, opt, params, cfg)
        return float(loss_fn(params))

    assert run(compressed=True) < 1e-3
