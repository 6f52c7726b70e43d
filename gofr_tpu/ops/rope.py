"""Rotary position embeddings (RoPE).

Functional, shape-static, position-indexed so the same code path serves
prefill (positions = arange) and decode (positions = per-sequence cursor),
which is what keeps the decode step a single compiled executable.

`scaling` (a config's `rope_scaling`, empty = none) is YaRN's
(factor, original_max_position_embeddings, beta_fast, beta_slow, mscale,
mscale_all_dim): the inverse frequencies of the long-wavelength pairs are
divided by the factor, the short ones kept, and the pairs between the two
correction dims blended by a linear ramp (DeepSeek-V3's
yarn_find_correction_range). cos and sin are scaled by
yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1 (1 at a factor <= 1)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, base: float, max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) / (2 * math.log(base))


def yarn_ramp(dim: int, base: float, max_pos: int, beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """gamma [dim // 2]: 1 where a pair keeps its frequency, 0 where it is
    interpolated, a linear ramp between the correction dims of beta_fast
    and beta_slow."""
    low = max(math.floor(_correction_dim(beta_fast, dim, base, max_pos)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, base, max_pos)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return 1.0 - ramp


def rope_frequencies(head_dim: int, theta: float = 10_000.0, scaling: tuple = ()) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta**exponent)
    if not scaling:
        return freqs
    factor, max_pos, beta_fast, beta_slow = scaling[:4]
    gamma = yarn_ramp(head_dim, theta, int(max_pos), beta_fast, beta_slow)
    return freqs / factor * (1.0 - gamma) + freqs * gamma


def apply_rope(
    x: jnp.ndarray,  # [batch, seq, heads, head_dim]
    positions: jnp.ndarray,  # [batch, seq] int32
    theta: float = 10_000.0,
    scaling: tuple = (),
) -> jnp.ndarray:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) by position*freq.

    Uses the "split halves" convention (as JAX/Gemma implementations do)
    rather than interleaved pairs; consistent across prefill/decode so the
    choice is unobservable from outside.
    """
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta, scaling)  # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, d/2]
    angles = angles[:, :, None, :]  # [b, s, 1, d/2] broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scaling:
        factor, _, _, _, mscale, mscale_all = scaling
        m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
        if m != 1.0:
            sin, cos = sin * m, cos * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)
