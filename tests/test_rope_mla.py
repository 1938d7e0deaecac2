"""Rope and MLA as DeepSeek-V2 publishes them: plain rope unchanged to the
bit, YaRN against the formulas of DeepSeek-V2's ``modeling_deepseek.py``,
and the RMSNorm on the compressed KV before it is cached."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RopeScaling
from repro.models.attention import KVCache, init_mla, mla_attention
from repro.models.common import apply_rope, rope_freqs, rms_norm
from repro.models.registry import get_config


def _parent_rope(x, positions, theta):
    """``apply_rope`` as it was before rope scaling existed."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs
    if x.ndim == angles.ndim + 1:
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def test_apply_rope_without_scaling_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 64), jnp.bfloat16)
    pos = jnp.asarray([[3], [700]]) + jnp.arange(9)[None]
    for theta in (10000.0, 500000.0):
        got = jax.jit(apply_rope, static_argnums=2)(x, pos, theta)
        want = jax.jit(_parent_rope, static_argnums=2)(x, pos, theta)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def _hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies, in
    float64."""
    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    return extra / factor * (1 - mask) + extra * mask, (low, high)


def test_yarn_matches_deepseek_formulas():
    cfg = get_config("deepseek-v2-lite-16b")
    rs = cfg.rope_scaling
    assert rs == RopeScaling(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    want, (low, high) = _hf_yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    assert (low, high) == (10, 23)
    got = rope_freqs(64, cfg.rope_theta, rs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    # below the range unscaled, above it divided by the factor
    np.testing.assert_allclose(np.asarray(got[:10]),
                               np.asarray(rope_freqs(64, 10000.0)[:10]))
    np.testing.assert_allclose(np.asarray(got[23:]) * 40.0,
                               np.asarray(rope_freqs(64, 10000.0)[23:]),
                               rtol=1e-6)
    # cos/sin factor mscale(40, 0.707) / mscale(40, 0.707) is 1: YaRN
    # rotates by the scaled frequencies alone
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 64), jnp.float32)
    pos = jnp.arange(5)
    ang = np.arange(5)[:, None, None] * want[None, None, :]
    x1, x2 = np.asarray(x[..., 0::2]), np.asarray(x[..., 1::2])
    rot = np.stack([x1 * np.cos(ang) - x2 * np.sin(ang),
                    x2 * np.cos(ang) + x1 * np.sin(ang)], -1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(apply_rope(x, pos, 10000.0, rs)),
                               rot, rtol=1e-4, atol=1e-5)


def test_mla_softmax_scale_carries_yarn_mscale():
    from repro.models.attention import mla_softmax_scale
    cfg = get_config("deepseek-v2-lite-16b")
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert abs(mscale ** 2 - 1.5896) < 1e-3
    assert math.isclose(mla_softmax_scale(cfg), 192 ** -0.5 * mscale ** 2)
    plain = get_config("deepseek-v2-236b")
    assert math.isclose(mla_softmax_scale(plain), 192 ** -0.5)


def test_mla_caches_the_normed_latent():
    """The compressed KV goes through its RMSNorm (``kv_norm``) before it
    is written to the cache, and the gain reaches the attention output."""
    cfg = get_config("deepseek-v2-lite-16b").reduced(dtype="float32")
    m = cfg.mla
    p = init_mla(jax.random.PRNGKey(2), cfg)
    p["kv_norm"]["norm_w"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), (m.kv_lora_rank,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, cfg.d_model))
    cache = KVCache(jnp.zeros((2, 16, m.kv_lora_rank)),
                    jnp.zeros((2, 16, m.qk_rope_dim)))
    out, new = mla_attention(p, x, cfg, positions=jnp.arange(6)[None],
                             cache=cache, cache_index=0)
    c_kv = (x @ p["w_dkv"])[..., :m.kv_lora_rank]
    want = rms_norm(c_kv, p["kv_norm"]["norm_w"], cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(new.k[:, :6]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(new.k[:, 6:]).max()) == 0.0
    p2 = dict(p, kv_norm={"norm_w": jnp.ones((m.kv_lora_rank,))})
    out2, _ = mla_attention(p2, x, cfg, positions=jnp.arange(6)[None],
                            cache=cache, cache_index=0)
    assert float(jnp.abs(out - out2).max()) > 1e-3
