"""Round-4 probe: does a W8A8 integer dot beat the W8A16 dequant-into-dot
(qmm) for the DECODE matvecs at bench shapes (B=128, int8 Gemma-2B)?

BASELINE.md r4 attribution: the 18-layer decode matvecs measure
~3.21 ms/step — above both the 2.44 ms int8 weight-stream bound and the
~2.6 ms bf16-MXU bound for W8A16. Hypothesis: the convert(int8)->bf16
inside the dot doesn't ride the MXU (same reason qmm_a8 wins prefill,
quant.py:72-81), so an s8 x s8 -> s32 dot with per-row dynamic activation
scales may pull the matvec cost toward the weight-stream bound.

Variants (delta method, chained chunks):
  w8a16  — the shipped decode_chunk path (qmm everywhere)
  w8a8   — qmm_a8 for all seven per-layer matvecs
  w8a8mlp— qmm_a8 for the three MLP matvecs only (75% of weight bytes)

Usage: python scripts/profile_w8a8_decode.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.models import TransformerConfig, init_params
from gofr_tpu.models.quant import qmm, qmm_a8, quantize_params
from gofr_tpu.models.transformer import (
    KVCache, _embed_tokens, _unembed_last, init_cache,
)
from gofr_tpu.ops import apply_rope, chunk_decode_attention, rms_norm

cfg = TransformerConfig.gemma_2b()
B, MAX, K, S = 128, 176, 16, 128
print("device:", jax.devices()[0].device_kind, flush=True)

params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
params = jax.jit(lambda p: quantize_params(p, cfg.dtype))(params)
_ = np.asarray(params["final_norm"])


def make_chunk(mm_attn, mm_mlp):
    L, hq, hkv, hd = cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def chunk(params, tokens, cache):
        b = tokens.shape[0]
        kb0 = jnp.zeros((L, b, K, hkv, hd), cache.k.dtype)
        vb0 = jnp.zeros((L, b, K, hkv, hd), cache.v.dtype)

        def step(carry, k_i):
            tok, kb, vb = carry
            positions = (cache.length + k_i)[:, None]
            x = _embed_tokens(params, cfg, tok[:, None])

            def layer(x, xs):
                lp, kc_l, vc_l, kb_l, vb_l = xs
                h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
                q = mm_attn(h, lp["wq"]).reshape(b, 1, hq, hd)
                kv = mm_attn(h, lp["wkv"]).reshape(b, 1, hkv, 2, hd)
                k_new, v_new = kv[:, :, :, 0], kv[:, :, :, 1]
                q = apply_rope(q, positions, cfg.rope_theta)
                k_new = apply_rope(k_new, positions, cfg.rope_theta)
                kb_l = jax.lax.dynamic_update_slice(
                    kb_l, k_new.astype(kb_l.dtype), (0, k_i, 0, 0))
                vb_l = jax.lax.dynamic_update_slice(
                    vb_l, v_new.astype(vb_l.dtype), (0, k_i, 0, 0))
                attn = chunk_decode_attention(
                    q, kc_l, vc_l, kb_l, vb_l, cache.length, k_i,
                    logit_cap=cfg.attn_logit_cap)
                x = x + mm_attn(attn.reshape(b, 1, hq * hd), lp["wo"]).astype(x.dtype)
                h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
                x = x + mm_mlp(
                    jax.nn.gelu(mm_mlp(h, lp["w_gate"])) * mm_mlp(h, lp["w_up"]),
                    lp["w_down"])
                return x, (kb_l, vb_l)

            x, (kb, vb) = jax.lax.scan(
                layer, x, (params["layers"], cache.k, cache.v, kb, vb))
            logits = _unembed_last(params, cfg, x)
            nt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nt, kb, vb), nt

        (last, kb, vb), toks = jax.lax.scan(
            step, (tokens, kb0, vb0), jnp.arange(K, dtype=jnp.int32))
        start = jnp.minimum(cache.length, MAX - K)
        merge = jax.vmap(
            lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (0, i, 0, 0)),
            in_axes=(1, 1, 0), out_axes=1)
        new_k = merge(cache.k, kb, start)
        new_v = merge(cache.v, vb, start)
        return toks, last, KVCache(k=new_k, v=new_v, length=cache.length + K)

    return jax.jit(chunk)


def time_chunk(name, chunk):
    cache = init_cache(cfg, B, MAX)
    cache = cache._replace(length=jnp.full((B,), S, jnp.int32))
    last = jnp.zeros((B,), jnp.int32)
    toks, last2, cache2 = chunk(params, last, cache)
    _ = np.asarray(last2)  # compile + sync
    totals = {}
    for n in (2, 8):
        c, l = cache, last
        t0 = time.perf_counter()
        for _i in range(n):
            toks, l, c = chunk(params, l, c)
            c = c._replace(length=jnp.full((B,), S, jnp.int32))
        _ = np.asarray(l)
        totals[n] = time.perf_counter() - t0
    per_step = (totals[8] - totals[2]) / 6 / K
    print(f"{name:28s} {per_step*1e3:7.3f} ms/step "
          f"({B/per_step/1e3:.1f}k tok/s)", flush=True)
    return per_step


w8a16 = time_chunk("w8a16 (shipped qmm)", make_chunk(qmm, qmm))
w8a8 = time_chunk("w8a8 all matvecs", make_chunk(qmm_a8, qmm_a8))
w8a8mlp = time_chunk("w8a8 mlp only", make_chunk(qmm, qmm_a8))
print(f"delta all: {(w8a16-w8a8)*1e3:+.3f} ms/step; "
      f"mlp-only: {(w8a16-w8a8mlp)*1e3:+.3f} ms/step", flush=True)
