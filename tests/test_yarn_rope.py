"""YaRN rope and softmax scale of latent attention at DeepSeek-V2's sizes
(rope dim 64, factor 40, original context 4096, beta 32/1, mscale 0.707),
against the published formulas written out here, and the interleaved-pair
rope layout against the published de-interleave then rotate-half."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import mla

CFG = get_config("deepseek-v2-236b")


def _published_inv_freq(dim=64, base=10000.0, factor=40.0, orig=4096,
                        beta_fast=32.0, beta_slow=1.0):
    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                   dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask, (low, high)


def test_correction_range_and_frequencies():
    rs = CFG.rope_scaling
    assert (rs.factor, rs.original_max_position_embeddings, rs.beta_fast,
            rs.beta_slow, rs.mscale, rs.mscale_all_dim) == (
        40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    want, (low, high) = _published_inv_freq()
    assert (low, high) == (10, 23)
    assert mla.yarn_find_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    inv, m = mla.yarn_frequencies(64, CFG.rope_theta, rs)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    # below the range the original frequencies, above it interpolated by 40
    np.testing.assert_allclose(inv[:10], 1.0 / 10000.0 ** (np.arange(0, 20, 2) / 64),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[23:] * 40.0,
                               1.0 / 10000.0 ** (np.arange(46, 64, 2) / 64),
                               rtol=1e-6)
    assert m == 1.0          # mscale / mscale_all_dim, equal here


def test_softmax_scale():
    mscale = 0.1 * 0.707 * math.log(40.0) + 1.0
    want = (128 + 64) ** -0.5 * mscale * mscale
    assert mla.yarn_get_mscale(40.0, 0.707) == mscale
    assert mla.softmax_scale(CFG) == pytest.approx(want, rel=1e-12)
    assert abs(want - 0.1147) < 1e-4
    plain = dataclasses.replace(CFG, rope_scaling=None)
    assert mla.softmax_scale(plain) == (128 + 64) ** -0.5


def test_interleaved_yarn_rope_matches_the_published_layout():
    s, h, d = 12, 2, 64
    x = jax.random.normal(jax.random.key(0), (1, s, h, d))
    pos = jnp.arange(s) + 3000
    got = np.asarray(mla.mla_rope(CFG, x, pos))
    # published: view(d//2, 2).transpose → rotate_half with cat(freqs, freqs)
    xn = np.asarray(x)
    xd = xn.reshape(1, s, h, d // 2, 2).swapaxes(-1, -2).reshape(1, s, h, d)
    inv, _ = _published_inv_freq()
    freqs = np.outer(np.asarray(pos, np.float32), inv)
    emb = np.concatenate([freqs, freqs], -1)
    cos, sin = np.cos(emb)[None, :, None], np.sin(emb)[None, :, None]
    rot = np.concatenate([-xd[..., d // 2:], xd[..., : d // 2]], -1)
    np.testing.assert_allclose(got, xd * cos + rot * sin, rtol=1e-4, atol=1e-4)


def test_plain_rope_is_unchanged_without_scaling():
    """Without YaRN the pairs, once de-interleaved, rotate as common rope."""
    from repro.models.common import apply_rope
    plain = dataclasses.replace(CFG, rope_scaling=None)
    x = jax.random.normal(jax.random.key(1), (2, 8, 4, 64))
    pos = jnp.arange(8)
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    np.testing.assert_array_equal(np.asarray(mla.mla_rope(plain, x, pos)),
                                  np.asarray(apply_rope(halves, pos, 10000.0)))


def test_rope_scaling_from_a_file_is_checked():
    """A config file's dict becomes ``RopeScaling``; a type the program does
    not implement is refused."""
    rs = dict(type="yarn", factor=40.0, mscale=0.707, mscale_all_dim=0.707)
    cfg = dataclasses.replace(CFG, rope_scaling=rs)
    assert cfg.rope_scaling.factor == 40.0
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, rope_scaling=dict(rs, type="linear"))
