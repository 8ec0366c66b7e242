"""Training substrate: loss descent, grad-accum equivalence, compression,
optimizer math, loss masking."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import api as mapi
from repro.models.module import init_params
from repro.optim import compression as comp
from repro.optim.adamw import AdamW, clip_by_global_norm, global_norm
from repro.train.loss import IGNORE, softmax_cross_entropy
from repro.train.step import init_state, make_train_step


@pytest.fixture()
def tiny():
    # function-scoped: donated buffers (donate_argnums) must never leak
    # between tests
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-1.7b")), n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128, vocab_size=128)
    params = init_params(jax.random.key(0), mapi.spec(cfg))
    return cfg, params


def _batch(cfg, b=4, s=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, cfg.vocab_size, (b, s + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


def test_loss_decreases(tiny):
    cfg, params = tiny
    opt = AdamW(lr=3e-3, warmup_steps=2, total_steps=60)
    state = init_state(params, opt)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    batch = _batch(cfg)   # overfit one batch
    losses = []
    for _ in range(40):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[::8]


def test_grad_accum_equivalent(tiny):
    cfg, params = tiny
    opt = AdamW(lr=1e-3, warmup_steps=1, total_steps=10)
    batch = _batch(cfg, b=8)
    s1 = init_state(params, opt)
    s2 = init_state(params, opt)
    step1 = jax.jit(make_train_step(cfg, opt, grad_accum=1))
    step4 = jax.jit(make_train_step(cfg, opt, grad_accum=4))
    s1, m1 = step1(s1, batch)
    s2, m4 = step4(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=2e-2)
    l1 = jax.tree.leaves(s1.params)
    l4 = jax.tree.leaves(s2.params)
    for a, b in zip(l1, l4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


def test_compressed_training_converges(tiny):
    cfg, params = tiny
    opt = AdamW(lr=3e-3, warmup_steps=2, total_steps=60)
    state = init_state(params, opt, compress=True)
    step = jax.jit(make_train_step(cfg, opt, compress=True),
                   donate_argnums=(0,))
    batch = _batch(cfg)
    losses = []
    for _ in range(40):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.75, losses[::8]


def test_quantize_roundtrip_bound(rng):
    x = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    q = comp.quantize(x)
    back = comp.dequantize(q)
    err = float(jnp.abs(back - x).max())
    assert err <= float(q.scale) * 0.5 + 1e-7


def test_error_feedback_accumulates(rng):
    g = {"w": jnp.asarray(rng.normal(size=(64,)).astype(np.float32))}
    ef = comp.ef_init(g)
    g_hat, ef2 = comp.ef_compress(g, ef)
    # residual = exactly the quantization error
    np.testing.assert_allclose(np.asarray(ef2.residual["w"]),
                               np.asarray(g["w"] - g_hat["w"]), atol=1e-7)


def test_compressed_psum_single_axis(rng):
    from jax.sharding import Mesh
    import numpy as onp
    mesh = Mesh(onp.array(jax.devices()[:1]), ("dp",))
    x = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    out = jax.jit(jax.shard_map(
        lambda v: comp.compressed_psum(v, "dp"), mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(None),
        out_specs=jax.sharding.PartitionSpec(None)))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=2e-2)


def test_clip_by_global_norm(rng):
    g = {"a": jnp.asarray(rng.normal(size=(128,)).astype(np.float32)) * 100}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    assert float(norm) > 1.0


def test_loss_masking():
    logits = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 4, 8)).astype(np.float32))
    labels = jnp.asarray([[1, 2, IGNORE, IGNORE], [3, IGNORE, IGNORE,
                                                   IGNORE]], jnp.int32)
    loss, acc = softmax_cross_entropy(logits, labels)
    # only 3 positions contribute
    lf = np.asarray(logits, np.float64)
    lse = np.log(np.exp(lf).sum(-1))
    want = ((lse[0, 0] - lf[0, 0, 1]) + (lse[0, 1] - lf[0, 1, 2])
            + (lse[1, 0] - lf[1, 0, 3])) / 3
    np.testing.assert_allclose(float(loss), want, rtol=1e-4)


def test_adamw_weight_decay_only_on_matrices():
    opt = AdamW(lr=1e-2, weight_decay=0.5, warmup_steps=1,
                lr_schedule="constant")
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = opt.init(params)
    grads = jax.tree.map(jnp.zeros_like, params)
    new_params, _ = opt.update(grads, state, params)
    assert float(new_params["w"][0, 0]) < 1.0   # decayed
    assert float(new_params["b"][0]) == 1.0     # not decayed
