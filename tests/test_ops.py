"""Ops tests: flash kernel (interpret mode) and decode attention against the
XLA reference — the test-oracle pattern the reference repo uses for its SQL
mocks (SURVEY.md §4: seams tested against a stand-in implementation)."""

import contextlib
import math

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.ops import (
    apply_rope,
    decode_attention,
    flash_attention,
    mha_reference,
    multi_head_attention,
    rms_norm,
)


def _qkv(b=2, sq=256, sk=256, hq=4, hkv=2, d=128, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        assert jnp.abs(ref - out).max() < 2e-5

    def test_gqa_group_indexing(self):
        # 8 query heads on 2 kv heads: head h reads kv group h // 4
        q, k, v = _qkv(hq=8, hkv=2, seed=3)
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert jnp.abs(ref - out).max() < 2e-5

    def test_logit_cap(self):
        q, k, v = _qkv(seed=5)
        ref = mha_reference(q, k, v, causal=True, logit_cap=50.0)
        out = flash_attention(q, k, v, causal=True, logit_cap=50.0, interpret=True)
        assert jnp.abs(ref - out).max() < 2e-5

    def test_rejects_untileable(self):
        q, k, v = _qkv(sq=100, sk=100)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, interpret=True)

    def test_dispatcher_falls_back_on_cpu(self):
        # On CPU backend the dispatcher must route to the reference path.
        q, k, v = _qkv(b=1, sq=128, sk=128)
        out = multi_head_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        assert jnp.abs(ref - out).max() < 1e-6


class TestDecodeAttention:
    def test_matches_masked_reference(self):
        b, max_len, hq, hkv, d = 2, 32, 4, 2, 16
        q, k, v = _qkv(b=b, sq=1, sk=max_len, hq=hq, hkv=hkv, d=d, seed=7)
        lengths = jnp.array([5, 32], jnp.int32)
        out = decode_attention(q, k, v, lengths)
        kv_mask = jnp.arange(max_len)[None, :] < lengths[:, None]
        ref = mha_reference(q, k, v, causal=False, kv_mask=kv_mask)
        assert jnp.abs(ref - out).max() < 1e-6


class TestRope:
    def test_position_zero_is_identity(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 8))
        pos = jnp.zeros((1, 1), jnp.int32)
        assert jnp.allclose(apply_rope(x, pos), x, atol=1e-6)

    def test_relative_property(self):
        # <rope(q, m), rope(k, n)> depends only on m - n
        d = 16
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, d))

        def dot_at(m, n):
            qm = apply_rope(q, jnp.array([[m]], jnp.int32))
            kn = apply_rope(k, jnp.array([[n]], jnp.int32))
            return float(jnp.sum(qm * kn))

        assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-4


class TestRMSNorm:
    def test_unit_rms_and_scale(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        out = rms_norm(x, jnp.zeros(64))
        rms = jnp.sqrt(jnp.mean(out**2, axis=-1))
        assert jnp.allclose(rms, 1.0, atol=1e-3)
        out2 = rms_norm(x, jnp.ones(64))  # (1 + 1) doubles
        assert jnp.allclose(out2, 2 * out, atol=1e-5)

    def test_bf16_stays_bf16(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.bfloat16)
        assert rms_norm(x, jnp.zeros(64, jnp.bfloat16)).dtype == jnp.bfloat16


def test_flash_sliding_window_matches_reference():
    """Banded flash kernel (Mistral sliding window): block-skipped kernel
    must equal the reference band mask, including queries whose whole
    window is inside one block and ones spanning block boundaries."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (2, 512, 4, 128)) for kk in ks)
    for window in (64, 128, 200, 511):
        ref = mha_reference(q, k, v, causal=True, window=window)
        out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        assert jnp.abs(ref - out).max() < 2e-5, window


def test_flash_window_multiple_of_block_skips_blocks():
    """Sanity at window == block size: the first K block of a late query
    block is fully dead and must be skipped without poisoning the
    running softmax (fully-masked-row guard)."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(kk, (1, 384, 2, 128)) for kk in ks)
    ref = mha_reference(q, k, v, causal=True, window=128)
    out = flash_attention(q, k, v, causal=True, window=128, interpret=True)
    assert jnp.abs(ref - out).max() < 2e-5


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("hkv", [1, 4, 8])
class TestPallasLowersForTpu:
    """Every Pallas entry point, cross-lowered for TPU on this CPU
    (``.trace().lower(lowering_platforms=("tpu",))``): the Pallas TPU
    block-shape rules run at lowering, no chip needed. The kernel tests
    above run ``interpret=True`` at head_dim 16, which applies none of
    them — a one-head block of the [NB, B, hkv, d] pool and a (1, 1) SMEM
    block of the [b, 1] offsets both passed there and lowered nowhere."""

    @staticmethod
    def _lower(fn, *structs):
        jax.jit(fn).trace(*structs).lower(lowering_platforms=("tpu",))

    @pytest.mark.parametrize("window", [0, 512])
    @pytest.mark.parametrize("chunk", [16, 64, 128])
    def test_flash(self, hkv, d, chunk, window):
        hq = 28 if hkv == 4 else 8 * hkv  # Qwen2's group of 7, else 8
        S = jax.ShapeDtypeStruct
        q = S((2, chunk, hq, d), jnp.bfloat16)
        k = S((2, chunk, hkv, d), jnp.bfloat16)
        self._lower(
            lambda q, k, v: flash_attention(
                q, k, v, window=window, block_q=chunk, block_k=chunk
            ),
            q, k, k,
        )
        # the chunked-prefill call: per-batch query offsets into a slot cache
        cache = S((2, 1024, hkv, d), jnp.bfloat16)
        self._lower(
            lambda q, k, v, o: flash_attention(
                q, k, v, window=window, block_q=chunk, q_offsets=o
            ),
            q, cache, cache, S((2,), jnp.int32),
        )

    # (block, lanes, table slots); the last is qwen2-7b.reason-closed's own
    # (with hkv 4 and d 128: hq 28, 16 lanes, a table of 120)
    @pytest.mark.parametrize("window", [0, 512])
    @pytest.mark.parametrize("pool", [jnp.bfloat16, jnp.int8])
    @pytest.mark.parametrize(
        "block,lanes,n_tbl", [(16, 2, 64), (64, 2, 16), (128, 2, 8), (16, 16, 120)]
    )
    def test_paged_decode(self, hkv, d, block, lanes, n_tbl, pool, window):
        from gofr_tpu.ops.attention import paged_chunk_decode_attention

        hq = 28 if hkv == 4 else 8 * hkv
        S = jax.ShapeDtypeStruct
        layers, n_blocks, steps = 3, 40, 8
        kp = S((layers, n_blocks, block, hkv * d), pool)  # the stack, as stored
        sc = S((layers, n_blocks, block, hkv), jnp.float32) if pool == jnp.int8 else None
        buf = S((lanes, steps, hkv, d), jnp.bfloat16)
        self._lower(
            lambda q, kp, vp, t, kb, vb, n, s, ly, ks, vs: paged_chunk_decode_attention(
                q, kp, vp, t, kb, vb, n, s, layer=ly, window=window,
                k_scales=ks, v_scales=vs, use_kernel=True,
            ),
            S((lanes, 1, hq, d), jnp.bfloat16), kp, kp, S((lanes, n_tbl), jnp.int32),
            buf, buf, S((lanes,), jnp.int32), S((), jnp.int32), S((), jnp.int32), sc, sc,
        )


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e host (none is attached): libtpu compiles
    for it ahead of time, which applies what lowering alone does not —
    Mosaic's own rules (slices aligned to the tiling, VMEM that fits).
    Described inside a fixture, in this one file: only the worker that
    runs these tests loads the TPU's library."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _persistent_cache_off():
    """A compile for a described chip can be written to the persistent
    compile cache but not read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


# lanes, hq, hkv, d, block, table slots, pool blocks, pool dtype, window
@pytest.mark.parametrize(
    "lanes,hq,hkv,d,block,n_tbl,n_blocks,pool,window",
    [
        (16, 28, 4, 128, 16, 120, 2394, jnp.bfloat16, 0),  # qwen2-7b.reason-closed
        (16, 28, 4, 128, 16, 120, 2394, jnp.int8, 0),
        (16, 28, 4, 128, 16, 113, 2394, jnp.bfloat16, 0),  # a last page group of one
        (16, 28, 4, 128, 64, 30, 600, jnp.bfloat16, 0),
        (16, 28, 4, 128, 128, 15, 300, jnp.int8, 0),
        (2, 8, 1, 128, 64, 16, 40, jnp.int8, 512),  # MQA, or one head of a TP shard
        (2, 64, 8, 256, 128, 8, 40, jnp.bfloat16, 512),
    ],
)
def test_paged_decode_compiles_for_the_v5e(
    v5e_chip, lanes, hq, hkv, d, block, n_tbl, n_blocks, pool, window
):
    """The paged-decode kernel through Mosaic for a v5e, and what the
    benchmark's roofline reader matches it by: the custom call is named
    paged_decode and its first operand is the 2-D s32 block table. Its pool
    operands are the program's own parameters, the stack as stored: the
    compiled program holds no array of a layer's pool's size but them."""
    from gofr_tpu.ops.attention import paged_chunk_decode_attention

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    layers = 28  # nothing is allocated; a few layers' pool XLA would park in VMEM
    kp = S((layers, n_blocks, block, hkv * d), pool)
    sc = S((layers, n_blocks, block, hkv), jnp.float32) if pool == jnp.int8 else None
    buf = S((lanes, 8, hkv, d), jnp.bfloat16)
    with _persistent_cache_off():
        text = jax.jit(
            lambda q, kp, vp, t, kb, vb, n, s, ly, ks, vs: paged_chunk_decode_attention(
                q, kp, vp, t, kb, vb, n, s, layer=ly, window=window,
                k_scales=ks, v_scales=vs, use_kernel=True,
            )
        ).lower(
            S((lanes, 1, hq, d), jnp.bfloat16), kp, kp, S((lanes, n_tbl), jnp.int32),
            buf, buf, S((lanes,), jnp.int32), S((), jnp.int32), S((), jnp.int32), sc, sc,
        ).compile().as_text()
    call = next(ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln)
    assert "%paged_decode" in call
    assert f"operand_layout_constraints={{s32[{lanes},{n_tbl}]" in call
    # the stack reaches the call as the parameter it is (k and v), and no
    # instruction of the compiled program yields an array as large as one
    # layer of it: nothing is sliced out or laid out again on the way
    dt = {jnp.bfloat16: "bf16", jnp.int8: "s8"}[pool]
    stack = f"{dt}[{layers},{n_blocks},{block},{hkv * d}]"
    assert call.split("operand_layout_constraints", 1)[1].count(stack) == 2
    pool_sized = [
        (op, shape) for op, shape in _hlo_results(text)
        if math.prod(shape) >= n_blocks * block * hkv * d
    ]
    assert sorted(pool_sized) == [("parameter", (layers, n_blocks, block, hkv * d))] * 2


@pytest.mark.parametrize("name,layers,n_blocks,window", [
    ("paged_decode", 3, 8208, 0),  # the full layers' pool: grows with the context
    ("paged_decode_window", 10, 240, 128),  # the window layers': 15 blocks a slot
])
def test_a_mixed_stacks_two_decode_calls_compile_for_the_v5e(v5e_chip, name, layers, n_blocks, window):
    """k-exaone-236b-a23b.mixed-closed's two kinds of decode call (16 lanes,
    64 / 8 heads of 128, 16-token blocks, a table of 520 slots): ONE kernel
    through Mosaic for a v5e under the name each kind gives it, which is what
    tells them apart in a device trace; each kind's pool, as stored, is the
    call's own operand."""
    from gofr_tpu.ops.attention import paged_chunk_decode_attention

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    lanes, hq, hkv, d, block, n_tbl = 16, 64, 8, 128, 16, 520
    kp = S((layers, n_blocks, block, hkv * d), jnp.bfloat16)
    buf = S((lanes, 8, hkv, d), jnp.bfloat16)
    with _persistent_cache_off():
        text = jax.jit(
            lambda q, kp, vp, t, kb, vb, n, s, ly: paged_chunk_decode_attention(
                q, kp, vp, t, kb, vb, n, s, layer=ly, window=window, use_kernel=True, name=name,
            )
        ).lower(
            S((lanes, 1, hq, d), jnp.bfloat16), kp, kp, S((lanes, n_tbl), jnp.int32),
            buf, buf, S((lanes,), jnp.int32), S((), jnp.int32), S((), jnp.int32),
        ).compile().as_text()
    call = next(ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln)
    assert f"%{name}." in call or f"%{name} " in call
    stack = f"bf16[{layers},{n_blocks},{block},{hkv * d}]"
    assert call.split("operand_layout_constraints", 1)[1].count(stack) == 2


def test_latent_paged_decode_compiles_for_the_v5e(v5e_chip):
    """The latent kernel at glm-4.7-flash.think-closed's own shapes (13 layers
    of 5,104 blocks, rows of 512 | 128, 20 heads, 16 lanes, a table of 200):
    through Mosaic for a v5e, its pool operands the program's parameters as
    stored, and nothing else in the program as large as a layer of them."""
    from gofr_tpu.ops.attention import mla_paged_chunk_decode_attention

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    layers, n_blocks, block, C, R, lanes, hq, n_tbl = 13, 5104, 16, 512, 128, 16, 20, 200
    with _persistent_cache_off():
        text = jax.jit(
            lambda q, cp, rp, t, cb, rb, n, s, ly: mla_paged_chunk_decode_attention(
                q, cp, rp, t, cb, rb, n, s, scale=0.1, layer=ly, use_kernel=True,
            )
        ).lower(
            S((lanes, 1, hq, C + R), jnp.bfloat16),
            S((layers, n_blocks, block, C), jnp.bfloat16), S((layers, n_blocks, block, R), jnp.bfloat16),
            S((lanes, n_tbl), jnp.int32), S((lanes, 8, 1, C), jnp.bfloat16), S((lanes, 8, 1, R), jnp.bfloat16),
            S((lanes,), jnp.int32), S((), jnp.int32), S((), jnp.int32),
        ).compile().as_text()
    call = next(ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln)
    assert "%mla_paged_decode" in call
    pool_sized = [
        (op, shape) for op, shape in _hlo_results(text) if math.prod(shape) >= n_blocks * block * R
    ]
    assert sorted(pool_sized) == [
        ("parameter", (layers, n_blocks, block, R)), ("parameter", (layers, n_blocks, block, C)),
    ]


def _hlo_results(text: str, dtype: str = "[a-z]+[0-9]*"):
    """(operation, result shape) of every instruction in a compiled
    program's text, a tuple's members each (those of `dtype`, where given)."""
    import re

    inst = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (?P<type>.*?) (?P<op>[a-z][a-z\-]*)\(")
    for ln in text.splitlines():
        m = inst.match(ln)
        if m:
            for dims in re.findall(rf"\b{dtype}\[([0-9,]*)\]", m["type"]):
                yield m["op"], tuple(int(x) for x in dims.split(",") if x)


# what hands an array on without making one: the entry's parameters, the
# loops that carry the stack and the views of it
_PASSED_ON = ("parameter", "tuple", "get-tuple-element", "while", "bitcast")


@pytest.mark.parametrize("program,rows,copies,writers,spare_gb", [
    ("rows", 4, 0, 1, 0.5),  # llm.step_p64_d0: the append alone
    ("chunk", 0, 2, 1, 0.9),  # llm.decode_chunk8: the end-of-chunk merge
    ("step", 4, 2, 2, 0.9),  # llm.step_p64_d8: both
    ("step", 2, 2, 2, 0.9),
])
def test_the_rings_programs_write_the_stack_where_it_lies(v5e_chip, program, rows, copies, writers, spare_gb):
    """mistral-7b.long-closed's three programs (32 layers, 4 slots, a ring of
    4,160 rows of 8 x 128, prompt rows of 64, abstract int8 weights) compiled
    for a v5e: a program that adds rows to the rolling ring writes THOSE rows
    into the donated `[L, S, C, hkv, hd]` stack in place. Of the
    instructions that yield a bf16 array of a whole stack's size, beside
    what only hands one on, there are the in-place scatters (a fusion and
    the scatter inside it, one pair an array a writer) and, where a decode
    chunk runs, the TWO copies that lay K and V out for the scan's per-head
    dots (PERF.md §5, ROADMAP S2: the physical layout's, not a writer's).
    No take of whole slots, no scan output of ring size, no transposed
    `[S, L, ...]` operand, no chained whole-stack dynamic-update-slice; and
    the temporaries beside those copies stay under `spare_gb`, which a
    scatter that is not in place (1.09 GB an array) cannot. The parent of
    PR 34 fails every case (7.2 GB of temporaries in the first)."""
    from gofr_tpu.kvcache import CacheManager
    from gofr_tpu.llm_programs import Programs
    from gofr_tpu.models.quant import quantize_params
    from gofr_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig.mistral_7b()
    S, K, c = 4, 8, 64
    kv = CacheManager(cfg, S, 8192, K, append_widths=(K, c), paged=False)
    assert (cfg.n_layers, kv.ring, cfg.n_kv_heads, cfg.head_dim) == (32, 4160, 8, 128)
    progs = Programs(
        cfg, kv, slots=S, decode_chunk=K, chunk_shapes=(c,), spec_draft=0, mesh=None,
        tp_gather=None, kernel=False, numeric_check=False, label="ring-v5e", metrics=None,
    )

    def abstract(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e_chip), tree)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = abstract(jax.eval_shape(
        lambda k: quantize_params(init_params(k, cfg), cfg.dtype), jax.random.PRNGKey(0)
    ))
    cache = abstract(jax.eval_shape(lambda: kv.init_cache(S)))
    rng = abstract(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    state = (spec((S,)), spec((S,), jnp.bool_), spec((S,), jnp.float32))  # tail, active, temps
    chunks, steps, _verify = progs.family(False)
    op, args = {
        "chunk": (chunks[K], (params, state[0], cache, *state[1:], rng)),
        "step": (steps[c], (params, cache, *state, spec((rows, c + 3)), spec((2, rows)), rng)),
        "rows": (progs.rows(False)[c], (params, cache, *state, spec((rows, c + 3)), spec((2, rows)), rng)),
    }[program]
    with _persistent_cache_off():
        compiled = op.lower(*args).compile()
    stack = math.prod(cache.k.shape)
    made = sorted(
        (o, shape) for o, shape in _hlo_results(compiled.as_text(), "bf16")
        if math.prod(shape) >= stack and o not in _PASSED_ON
    )
    flat = (stack // (cfg.n_kv_heads * cfg.head_dim), cfg.n_kv_heads, cfg.head_dim)  # XLA merges (L, S, C) itself
    assert made == sorted(
        [("copy", cache.k.shape)] * copies + [("fusion", flat), ("scatter", flat)] * 2 * writers
    ), made
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries - copies * 2 * stack < spare_gb * 1e9, temporaries


@pytest.mark.parametrize("hkv", [4, 1, 2])  # kv sharded / MQA / replicated
def test_kernels_under_a_tp_mesh_match_single_device(hkv):
    """Mosaic kernels cannot be partitioned by GSPMD, so under a TP mesh
    each runs inside a shard_map over its heads (ops.attention._head_axes).
    Interpret mode on the virtual mesh: same values as with no mesh."""
    import numpy as np

    from gofr_tpu.kvcache.paged import quantize_rows, stored_rows
    from gofr_tpu.ops.attention import paged_chunk_decode_attention
    from gofr_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 1, "model": 4}, devices=jax.devices()[:4])
    hq, d, b = 8, 128, 2
    q, k, v = _qkv(b=b, sq=128, sk=256, hq=hq, hkv=hkv, d=d)
    offs = jnp.asarray([0, 97], jnp.int32)
    for kw in ({}, {"q_offsets": offs}):
        kk = k if kw else k[:, :128]
        vv = v if kw else v[:, :128]
        want = flash_attention(q, kk, vv, interpret=True, **kw)
        got = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, interpret=True, mesh=mesh, **kw)
        )(q, kk, vv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    rng = np.random.RandomState(0)
    block, n_tbl, n_blocks, steps = 16, 4, 12, 4
    layers = 2
    pk = jnp.asarray(rng.randn(layers, n_blocks, block, hkv, d).astype(np.float32))
    pv = jnp.asarray(rng.randn(layers, n_blocks, block, hkv, d).astype(np.float32))
    tables = jnp.asarray(rng.randint(0, n_blocks, size=(b, n_tbl)).astype(np.int32))
    kb = jnp.asarray(rng.randn(b, steps, hkv, d).astype(np.float32))
    vb = jnp.asarray(rng.randn(b, steps, hkv, d).astype(np.float32))
    lengths, step = jnp.asarray([13, 50], jnp.int32), jnp.asarray(2, jnp.int32)
    (qk, sk), (qv, sv) = quantize_rows(pk), quantize_rows(pv)
    for kp, vp, ks, vs in ((pk, pv, None, None), (qk, qv, sk, sv)):
        kp, vp = stored_rows(kp), stored_rows(vp)  # the stack as stored

        def attend(mesh):
            return jax.jit(
                lambda q, kp, vp, ks, vs: paged_chunk_decode_attention(
                    q, kp, vp, tables, kb, vb, lengths, step, layer=1, k_scales=ks,
                    v_scales=vs, use_kernel=True, interpret=True, mesh=mesh,
                )
            )(q[:, :1], kp, vp, ks, vs)

        np.testing.assert_allclose(
            np.asarray(attend(mesh)), np.asarray(attend(None)), atol=1e-6
        )
