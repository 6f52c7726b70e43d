"""The engine's device programs, held to the text they lowered to at PR 30.

PR 31 moved program construction out of `LLMEngine.__init__` into
`gofr_tpu/llm_programs.py` and wrote each of chunk, step and verify once, over
a layout and a sampler, where twelve mirrored factories stood. The claim is
that no program changed. Every program of six tiny engines is lowered here
with the arguments `_warm` passes (`InstrumentedJit.lower(*args).as_text()`)
and its sha256 compared with `tests/data/engine_programs_pr30.json`, which this
file's `__main__` wrote on commit 1bef508, before the refactor. Three engines
are shaped like the benchmark's cells (paged GQA with int8 weights and a
prefix index; a windowed ring; latent + routed, paged), three cover what
those leave out (an int8 pool, a slab with a prefix cache and LoRA slots, a
routed GQA model). The pins retire with PR 29's (ROADMAP D12) at the first PR
that means to change a program.

PR 32 added a program and changed none: a step without its decode chunk
(`llm.step_p{n}_d0`, `Programs.rows`). Those of the same six engines are
pinned in `tests/data/engine_programs_pr32.json` (written on PR 32's tree by
this file's `__main__`, which leaves PR 30's file alone) and are cases of the
same test.

PR 33 changed none of them either (one scalar window and "every expert held"
are the special cases of the per-layer pattern and of the expert share) and
added an engine: `mixed`, the tiny twin of a stack of window and full layers
over two pools that holds a share of its experts. Its programs are pinned in
`tests/data/engine_programs_pr33.json`, written on PR 33's tree.

PR 34 is the first that MEANS to change pinned programs, those of the
contiguous layouts (`llm_programs._Slab`): a program that adds rows writes
them into the donated stack where it lies. The 22 `mistral.*` programs that
append, verify or merge (every step, the verify, the ring's decode chunks)
and the 18 `slab.*` ones that append or verify are listed in `CHANGED` with
the reason, must differ from their old pins, and are held to their new text
in `tests/data/engine_programs_pr34.json` (this file's `__main__`, on PR 34's
tree). The dense slab's decode chunks (`slab.chunk*`: the `ring == 0` merge,
which the pool's off-TPU decode shares), every wave-path program and every
program of the five paged engines pass byte for byte.

PR 36 changed none of them (the cache's `state` is None where no layer is
linear, a pytree node without leaves) and added an engine: `olmo`, the tiny
twin of a stack of gated DeltaNet and full MHA layers over the paged pool and
a per-slot recurrent state. Its programs are pinned in
`tests/data/engine_programs_pr36.json`, written on PR 36's tree by
`python tests/test_engine_programs.py olmo`.

The sparse latent engine changed none of them (no indexer, one routing
group and no rope scaling lower as before) and added an engine: `dsa`, the
tiny twin of DeepSeek-V3.2's block (latent attention with a lightning indexer
that picks each query's rows, YaRN rope, group-limited routing over a held
share of the experts). Its programs are pinned in
`tests/data/engine_programs_dsa.json`, written by
`python tests/test_engine_programs.py dsa`.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.llm import LLMEngine
from gofr_tpu.models.quant import quantize_params
from gofr_tpu.models.transformer import TransformerConfig, init_params

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
_PINS = os.path.join(_DATA, "engine_programs_pr30.json")
_PINS_D0 = os.path.join(_DATA, "engine_programs_pr32.json")  # the step programs without their decode chunk
_PINS_MIXED = os.path.join(_DATA, "engine_programs_pr33.json")  # the mixed engine's, all of them
_PINS_IN_PLACE = os.path.join(_DATA, "engine_programs_pr34.json")  # the contiguous layouts' programs PR 34 changed
_PINS_LINEAR = os.path.join(_DATA, "engine_programs_pr36.json")  # the linear stack's engine, all of them
_PINS_SPARSE = os.path.join(_DATA, "engine_programs_dsa.json")  # the sparse latent engine, all of them

_KW = dict(
    slots=4, max_seq_len=128, prefill_buckets=(16, 64), decode_chunk=8,
    speculative=True, warmup=False,
)
# engine -> (preset, keywords); the first three are the cells' shapes
ENGINES = {
    "qwen2": ("tiny_qwen2", dict(_KW, quantize=True, prefix_cache_mb=1, kv_paged=True)),
    "mistral": ("tiny_mistral", dict(_KW, quantize=True, kv_paged=False)),
    "glm": ("tiny_latent_moe", dict(_KW, quantize=True, prefix_cache_mb=1, speculative=False)),
    "kv8": ("tiny_qwen2", dict(_KW, kv_paged=True, kv_int8=True, session_mb=1)),
    "slab": ("tiny_llama", dict(_KW, kv_paged=False, prefix_cache_mb=1, lora_slots=2)),
    "moe": ("tiny_moe", dict(_KW, kv_paged=True)),
    "mixed": ("tiny_mixed_moe", dict(_KW, quantize=True, speculative=False)),
    "olmo": ("tiny_olmo_hybrid", dict(_KW, quantize=True, speculative=False)),
    "dsa": ("tiny_dsa_moe", dict(_KW, quantize=True, prefix_cache_mb=1, speculative=False)),
}
# The ONLY programs whose text may differ from the parent's, and why: the
# parent's constrained paged copies were not given PR 29's `moe_out`, so a
# routed model's grammar programs returned no expert vector and
# stats()["moe"] missed their layer calls. The one body returns it for both
# samplers. (A dense or latent model's programs carry nothing of the kind.)
CHANGED = {
    f"moe.{p}": "a routed model's grammar program now returns the expert vector its plain twin returns"
    for p in ("chunk2g", "chunk8g", "step_p16_n1g", "step_p16_n4g", "step_p64_n1g", "step_p64_n4g")
}
# PR 34, the contiguous layouts: every step (fused or `_d0`, plain or
# grammar) and the verify of the ring and of the slab, and the ring's decode
# chunks. Re-pinned in _PINS_IN_PLACE.
_STEPS = [
    f"step_p{shape}_n{nb}{g}{d0}"
    for shape in (16, 64) for nb in (1, 4) for g in ("", "g") for d0 in ("", "_d0")
]
IN_PLACE = {
    **{
        f"{eng}.{p}": "the append scatters the chunk's rows into the donated stack: no take of whole "
        "slots, no scan output of ring size, no whole-slot write-back"
        for eng in ("mistral", "slab") for p in _STEPS
    },
    **{
        f"{eng}.{p}": "verify_chunk follows the append: the stack and every slot, the drafts' rows scattered in place"
        for eng in ("mistral", "slab") for p in ("step_v", "step_vg")
    },
    **{
        f"mistral.{p}": "the ring's end-of-chunk merge is one scatter over the stored shape, not a vmap over the slot axis"
        for p in ("chunk2", "chunk2g", "chunk8", "chunk8g")
    },
}
CHANGED.update(IN_PLACE)


def _build(name: str) -> LLMEngine:
    preset, kw = ENGINES[name]
    cfg = getattr(TransformerConfig, preset)()
    params = init_params(jax.random.PRNGKey(0), cfg)
    if kw.get("quantize"):
        params = quantize_params(params, cfg.dtype)
    return LLMEngine(cfg, params, **kw)


def programs(eng: LLMEngine) -> dict:
    """name -> (program, arguments): every jitted program of the engine with
    the stand-ins `_warm` hands it."""
    S, M, V = eng.slots, eng.admit_cap, eng.cfg.vocab_size
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    tail, active, temps = i32(S), jnp.zeros((S,), bool), jnp.zeros((S,), jnp.float32)
    rng, cache, params = eng._rng, eng.cache, eng.params
    meta = i32(3, M)
    paged = eng.kv.paged
    if paged:
        scales = eng._kv_scales
        tables, live = i32(S, eng.kv.table_cols), jnp.zeros((S,), bool)
        pool, pool_live = (cache, scales, tables), (cache, scales, tables, live)
    else:
        pool = pool_live = (cache,)
    out = {
        "prefill_b16_n1": (eng._prefill_op, (params, i32(1, 18), rng)),
        "admit_update_n1": (eng._admit_update, (tail, active, temps, i32(1), meta)),
    }
    if eng.kv.linear:  # the wave scheduler's whole-prompt prefill: refused, no such form
        del out["prefill_b16_n1"]
    if eng._hit_first_op is not None:
        out["hit_first_n1"] = (
            eng._hit_first_op, (jnp.zeros((1, V), jnp.float32), jnp.zeros((1,), jnp.float32), rng)
        )
    two_kinds = eng.kv.mixed or eng.kv.linear
    scratch = None if two_kinds else eng.kv.init_cache(1)
    if two_kinds:
        pass  # insert, seed and restore are written for one kind of state and never run for two
    elif paged:
        out["insert_many_n1"] = (eng._insert_many, (cache, scales, scratch, meta[:2], tables))
        out["kv_seed"] = (eng._seed_op, (cache, scales, i32(M), i32(M), i32(M), i32(M)))
        L, B = cache.k.shape[0], eng.kv.block
        (hk, dk), (hv, dv) = eng.kv.row_shapes
        hs = jnp.zeros((2, L, 2, B, hk), jnp.float32) if eng.kv.int8 else jnp.zeros((0,), jnp.float32)
        out["kv_restore2"] = (eng._programs.restore_op(2), (
            cache, scales, jnp.zeros((L, 2, B, hk, dk), cache.k.dtype),
            jnp.zeros((L, 2, B, hv, dv), cache.v.dtype), hs, i32(2),
        ))
    else:
        out["insert_many_n1"] = (eng._insert_many, (cache, scratch, meta))
    gids, gstate, gtab = i32(S), i32(S), jnp.full((2, 8, V), -1, jnp.int32)
    families = [("", eng._chunk_ops, eng._step_ops, eng._verify_op)]
    if eng.constrained:
        families.append(("g", *eng._ops(True)))
    for g, chunk_ops, step_ops, verify_op in families:
        for shape, op in sorted(step_ops.items()):
            for nb in (1, M):
                smeta = jnp.full((4 if g else 2, nb), S, jnp.int32).at[1].set(0)
                state = (tail, active, temps) + ((gstate,) if g else ())
                out[f"step_p{shape}_n{nb}{g}"] = (op, (
                    params, *pool_live, *state, i32(nb, shape + 3), smeta,
                    *((gids, rng, gtab) if g else (rng,)),
                ))
                # the rows alone: no `live` mask, no lanes' grammar ids
                out[f"step_p{shape}_n{nb}{g}_d0"] = (eng._programs.rows(bool(g))[shape], (
                    params, *pool, *state, i32(nb, shape + 3), smeta,
                    *((rng, gtab) if g else (rng,)),
                ))
        if verify_op is not None:
            out[f"step_v{g}"] = (verify_op, (
                params, *pool, tail, temps, *((gstate,) if g else ()),
                i32(S, eng.spec_draft + 2), *((gids, rng, gtab) if g else (rng,)),
            ))
        for K, op in sorted(chunk_ops.items()):
            out[f"chunk{K}{g}"] = (op, (
                params, tail, *pool_live, active, temps,
                *((gstate, gids, rng, gtab) if g else (rng,)),
            ))
    return out


def _sha(op, args) -> str:
    return hashlib.sha256(op.lower(*args).as_text().encode()).hexdigest()[:16]


PINNED: dict = {}
for _path in (_PINS, _PINS_D0, _PINS_MIXED, _PINS_LINEAR, _PINS_SPARSE):
    with open(_path) as _f:
        PINNED.update(json.load(_f))

_engines: dict = {}


def _engine_programs(name: str) -> dict:
    # one engine a name for the whole file: nothing is compiled or run
    if name not in _engines:
        _engines[name] = programs(_build(name))
    return _engines[name]


@pytest.mark.parametrize("key", sorted(PINNED))
def test_a_program_lowers_to_the_text_it_lowered_to_at_pr30(key):
    engine, name = key.split(".", 1)
    op, args = _engine_programs(engine)[name]
    got = _sha(op, args)
    if key in CHANGED:
        assert got != PINNED[key], f"{key} is listed as changed ({CHANGED[key]}) and is not"
    else:
        assert got == PINNED[key], key


with open(_PINS_IN_PLACE) as _f:
    REPINNED = json.load(_f)


@pytest.mark.parametrize("key", sorted(IN_PLACE))
def test_a_program_pr34_changed_lowers_to_its_new_text(key):
    engine, name = key.split(".", 1)
    assert _sha(*_engine_programs(engine)[name]) == REPINNED[key], key


def test_every_program_of_the_six_engines_is_pinned():
    have = {f"{e}.{p}" for e in ENGINES for p in _engine_programs(e)}
    assert have == set(PINNED)
    assert set(CHANGED) <= have
    assert set(REPINNED) == set(IN_PLACE)


if __name__ == "__main__" and sys.argv[1:] in (["olmo"], ["dsa"]):  # writes the linear or the sparse engine's pins
    name = sys.argv[1]
    pins = {f"{name}.{p}": _sha(op, args) for p, (op, args) in _engine_programs(name).items()}
    with open({"olmo": _PINS_LINEAR, "dsa": _PINS_SPARSE}[name], "w") as f:
        json.dump(dict(sorted(pins.items())), f, indent=1)
        f.write("\n")
    print(len(pins), "programs pinned")
elif __name__ == "__main__":  # PYTHONPATH=. python tests/test_engine_programs.py: writes the re-pins of PR 34 (on its tree)
    pins = {}
    for key in IN_PLACE:
        engine, name = key.split(".", 1)
        pins[key] = _sha(*_engine_programs(engine)[name])
    with open(_PINS_IN_PLACE, "w") as f:
        json.dump(dict(sorted(pins.items())), f, indent=1)
        f.write("\n")
    print(len(pins), "programs pinned")
