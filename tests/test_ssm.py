"""Chunked Mamba2/RWKV6 forward == naive sequential recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import ssm
from repro.models.config import MambaConfig, ModelConfig, RWKVConfig


def _mamba_cfg(chunk):
    return ModelConfig(
        arch_id="t", family="ssm", num_layers=1, d_model=64, num_heads=2,
        num_kv_heads=2, head_dim=32, d_ff=128, vocab_size=64,
        block_pattern=("mamba",),
        mamba=MambaConfig(state_dim=8, head_dim=32, expand=2, chunk=chunk, conv_width=4),
    )


def test_mamba_chunked_equals_sequential_decode():
    """Prefill (chunked SSD) must equal running decode step by step."""
    cfg = _mamba_cfg(chunk=8)
    key = jax.random.PRNGKey(0)
    params = ssm.mamba_init(cfg, key)
    B, S = 2, 32
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model), jnp.float32) * 0.5
    y_chunked = ssm.mamba_apply(cfg, params, x)
    cache = ssm.mamba_cache_init(cfg, B)
    ys = []
    for t in range(S):
        yt, cache = ssm.mamba_decode(cfg, params, x[:, t : t + 1], cache, jnp.int32(t))
        ys.append(yt)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(
        np.asarray(y_chunked, np.float32), np.asarray(y_seq, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("chunks", [(8, 16)])
def test_mamba_chunk_size_invariance(chunks):
    c1, c2 = chunks
    key = jax.random.PRNGKey(2)
    B, S = 1, 32
    cfg1, cfg2 = _mamba_cfg(c1), _mamba_cfg(c2)
    params = ssm.mamba_init(cfg1, key)
    x = jax.random.normal(jax.random.fold_in(key, 3), (B, S, cfg1.d_model)) * 0.5
    y1 = ssm.mamba_apply(cfg1, params, x)
    y2 = ssm.mamba_apply(cfg2, params, x)
    np.testing.assert_allclose(np.asarray(y1, np.float32), np.asarray(y2, np.float32), rtol=1e-3, atol=1e-3)


def test_mamba_grad_finite_at_published_chunk():
    """At chunk 256 the acausal half of the decay exponents overflows exp;
    it must be masked before the exp, or its zero cotangent times inf turns
    every gradient upstream of the SSD into NaN."""
    cfg = _mamba_cfg(chunk=256)
    key = jax.random.PRNGKey(4)
    params = ssm.mamba_init(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 5), (1, 256, cfg.d_model)) * 0.5
    g = jax.grad(lambda p: jnp.sum(ssm.mamba_apply(cfg, p, x) ** 2))(params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(g):
        assert np.all(np.isfinite(np.asarray(leaf))), jax.tree_util.keystr(path)


def _rwkv_cfg():
    return ModelConfig(
        arch_id="t", family="ssm", num_layers=1, d_model=64, num_heads=2,
        num_kv_heads=2, head_dim=32, d_ff=128, vocab_size=64,
        block_pattern=("rwkv",), use_rope=False,
        rwkv=RWKVConfig(head_dim=32, decay_lora=8),
    )


def test_rwkv_chunked_equals_sequential_decode():
    cfg = _rwkv_cfg()
    key = jax.random.PRNGKey(4)
    params = ssm.rwkv_init(cfg, key)
    B, S = 2, 64  # 2 chunks of 32
    x = jax.random.normal(jax.random.fold_in(key, 5), (B, S, cfg.d_model), jnp.float32) * 0.5
    y_chunked = ssm.rwkv_timemix_apply(cfg, params, x)
    cache = ssm.rwkv_cache_init(cfg, B)
    ys = []
    c = {"state": cache["state"], "x_last": cache["x_last"]}
    for t in range(S):
        yt, c = ssm.rwkv_timemix_decode(cfg, params, x[:, t : t + 1], c, jnp.int32(t))
        ys.append(yt)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(
        np.asarray(y_chunked, np.float32), np.asarray(y_seq, np.float32), rtol=3e-2, atol=3e-2
    )


def test_rwkv_decay_clamped():
    """log w must live in [RWKV_LOGW_MIN, RWKV_LOGW_MAX] (stability contract)."""
    cfg = _rwkv_cfg()
    params = ssm.rwkv_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model)) * 10.0
    rv, kv, vv, logw, g = ssm._rwkv_proj(cfg, params, x, ssm._shift(x))
    lw = np.asarray(logw, np.float32)
    assert (lw >= ssm.RWKV_LOGW_MIN - 1e-6).all() and (lw <= 0).all()


def test_mamba_state_shape():
    cfg = _mamba_cfg(8)
    cache = ssm.mamba_cache_init(cfg, batch=3)
    d_inner = cfg.mamba.expand * cfg.d_model
    H = d_inner // cfg.mamba.head_dim
    assert cache["ssm"].shape == (3, H, cfg.mamba.state_dim, cfg.mamba.head_dim)
    assert cache["conv"].shape == (3, cfg.mamba.conv_width - 1, d_inner + 2 * cfg.mamba.state_dim)
