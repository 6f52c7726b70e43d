"""A step runs its decode chunk only when a lane decodes (PR 32).

`_dispatch_step` sends the prompt rows alone (`llm.step_p{n}_d0`, k = 0) when
no lane decodes and no row finishes its prompt: every result of the chunk
would have been masked. What must hold, on tiny twins of the benchmark's
layouts (the rolling ring, the dense slab, the paged pool with GQA and with
the latent cache and routed experts), with and without a grammar:

- greedy tokens are those of fused steps only, which is what every step was
  before: the same request served beside a decoding lane rides fused steps
  alone, and alone on an idle engine it rides `_d0` steps up to its last row;
- the choice, from the step records: k == 0 and a `_d0` program exactly when
  no lane decoded and no row finished;
- a request that arrives at an idle, warmed engine compiles nothing;
- `lookahead` bounds the steps in flight whatever their kind.
"""

import threading
import time

import jax
import numpy as np
import pytest

from gofr_tpu.llm import GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, generate, init_params
from gofr_tpu.structured import compile_json_schema

V = 128
# char-level vocabulary (tests/test_structured.py): id i -> printable byte, last id = eos
VOCAB = [chr(0x20 + i).encode() if 0x20 + i < 0x7F else b"" for i in range(V - 1)] + [b""]
SCHEMA = {"type": "object", "properties": {"name": {"type": "string", "maxLength": 6}, "n": {"type": "integer"}}}
CHUNK, K = 16, 8
_KW = dict(slots=2, max_seq_len=160, prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
           step_token_budget=CHUNK, decode_chunk=K, warmup=False)
# layout -> (preset, keywords, the standalone generate() can follow it)
LAYOUTS = {
    "ring": ("tiny_mistral", dict(_KW), True),  # window 8: a 40-token prompt rolls the ring
    "slab": ("tiny", dict(_KW, kv_paged=False), True),
    "pool": ("tiny_qwen2", dict(_KW, kv_paged=True), True),
    "latent": ("tiny_latent_moe", dict(_KW), False),
}
# one more contiguous layout, for the test of the stack written in place (PR 34)
# alone: the dense slab beside what else reads and seeds it
SLAB_PLUS = {"slab-prefix-lora": ("tiny_llama", dict(_KW, kv_paged=False, prefix_cache_mb=1, lora_slots=2), True)}
PROMPT = np.random.default_rng(32).integers(1, V - 1, 40).tolist()  # rows of 16, 16 and 8
SHORT = [5, 9, 2, 7]


@pytest.fixture(scope="module")
def grammar():
    return compile_json_schema(SCHEMA, VOCAB, V - 1)


_built: dict = {}


def _layout(layout):
    return LAYOUTS.get(layout) or SLAB_PLUS[layout]


def _model(layout):
    if layout not in _built:
        cfg = getattr(TransformerConfig, _layout(layout)[0])(vocab_size=V)
        _built[layout] = cfg, init_params(jax.random.PRNGKey(1), cfg)
    return _built[layout]


def _engine(layout, **kw) -> LLMEngine:
    cfg, params = _model(layout)
    return LLMEngine(cfg, params, **{**_layout(layout)[1], **kw})


def _generated(layout, prompt, n) -> list[int]:
    """The standalone generate()'s greedy tokens for one prompt."""
    cfg, params = _model(layout)
    toks = np.zeros((1, 64), np.int32)
    toks[0, : len(prompt)] = prompt
    want = generate(params, cfg, jax.numpy.asarray(toks), jax.numpy.asarray([len(prompt)], jax.numpy.int32), n)
    return [int(t) for t in np.asarray(want)[0]]


def _steps(eng, since=0) -> list[dict]:
    """The step records (kind `step`) with seq > since, in dispatch order
    (the ring is in the collector's order, which lets first tokens jump)."""
    log = eng.stats()["step_log"]
    recs = [dict(zip(log["fields"], r)) for r in log["records"]]
    return sorted((r for r in recs if r["kind"] == "step" and r["seq"] > since), key=lambda r: r["seq"])


def _settle(eng) -> int:
    """Every dispatched program's record is closed; the last seq."""
    for _ in range(400):
        with eng._lock:
            idle = not eng._inflight and eng._processing is None
        if idle:
            break
        time.sleep(0.01)
    log = eng.stats()["step_log"]
    return max((r[0] for r in log["records"]), default=0)


def _the_rule_holds(recs) -> None:
    for r in recs:
        d0 = r["program"].rstrip("g").endswith("_d0")
        assert d0 == (r["k"] == 0) == (r["lanes"] == 0), r
        if d0:  # nothing decoded, nothing finished: no token came out of it
            assert r["emitted"] == 0 and r["decode_ctx"] == () and r["rows"], r
        else:
            assert r["k"] == K, r


@pytest.mark.parametrize("constrained", [False, True], ids=["plain", "grammar"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_greedy_tokens_are_those_of_fused_steps(layout, constrained, grammar):
    if constrained and layout == "latent":
        pytest.skip("the latent family refuses constrained decoding at engine build")
    g = grammar if constrained else None
    n = 24 if constrained else 8
    eng = _engine(layout)
    try:
        # a first occupant finishes: its slot's device `active` flag stays True
        assert len(eng.generate(SHORT, max_new_tokens=2)) == 2
        seq0, d0_0 = _settle(eng), eng.stats()["steps_without_decode"]
        alone = eng.generate(PROMPT, max_new_tokens=n, grammar=g)
        seq1, d0_1 = _settle(eng), eng.stats()["steps_without_decode"]
        mine = [r for r in _steps(eng, seq0) if r["rows"]]
        assert [r["program"].rstrip("g")[-3:] for r in mine] == ["_d0", "_d0", f"_d{K}"]
        assert [r["rows"][0][:2] for r in mine] == [(0, 16), (16, 16), (32, 8)]
        assert d0_1 - d0_0 == 2
        # the same request beside a decoding lane: every step is the fused program
        decoder = eng.submit(GenRequest(SHORT, max_new_tokens=120))
        while decoder.emitted == 0:
            time.sleep(0.005)
        beside = eng.generate(PROMPT, max_new_tokens=n, grammar=g)
        assert len(decoder.tokens(timeout=120)) == 120
        _settle(eng)
        assert eng.stats()["steps_without_decode"] == d0_1
        assert all(r["k"] == K for r in _steps(eng, seq1))
        assert alone == beside
        if LAYOUTS[layout][2] and not constrained:
            assert alone == _generated(layout, PROMPT, n)
        _the_rule_holds(_steps(eng))
    finally:
        eng.close()


@pytest.mark.parametrize("layout", ["ring", "pool"])
def test_the_choice_follows_the_lanes_and_the_finishing_rows(layout):
    """Several requests at once, prompts of one to four rows: a step is
    decode-free exactly when no lane decoded and no row finished in it; a
    finishing row and a decoding lane always ride the fused program."""
    from gofr_tpu.metrics import new_metrics_manager

    metrics = new_metrics_manager()
    eng = _engine(layout, slots=4, step_token_budget=2 * CHUNK, metrics=metrics)
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, V - 1, n).tolist() for n in (50, 3, 33, 64, 17, 40)]
        outs: list = [None] * len(prompts)

        def run(i):
            outs[i] = eng.generate(prompts[i], max_new_tokens=4 + 3 * i)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert [len(o) for o in outs] == [4 + 3 * i for i in range(len(prompts))]
        _settle(eng)
        recs, st = _steps(eng), eng.stats()
        _the_rule_holds(recs)
        d0 = [r for r in recs if r["k"] == 0]
        assert d0 and len(d0) < len(recs)  # the first rows of a cold engine; never the last
        assert st["steps_without_decode"] == len(d0) and st["steps"] == len(recs)
        # step_tokens counts a decode-free step's prompt tokens and nothing else
        assert st["step_tokens"] == sum(
            sum(n for _c, n, _s in r["rows"]) + r["k"] * r["lanes"] for r in recs
        )
        expo = metrics.render_prometheus()
        assert f'app_llm_steps_without_decode_total{{model="{eng.label}"}} {len(d0)}' in expo
    finally:
        eng.close()


@pytest.mark.parametrize("layout", ["ring", "slab", "slab-prefix-lora"])
def test_a_contiguous_stack_written_in_place_serves_what_generate_does(layout):
    """Six requests over four slots, two packed rows a step (so a step's rows
    are a subset of the slots in the scheduler's order, padded to a width,
    the last row of a prompt shorter than its chunk), prompts of up to 64
    tokens over a ring of 24 (it rolls more than twice), lanes that finish
    early and ride the following merges inactive: every request's greedy
    tokens are the standalone generate()'s, and again on a second pass
    (where a prefix cache is on, its hits seed the slots)."""
    eng = _engine(layout, slots=4, step_token_budget=2 * CHUNK)
    try:
        if layout == "ring":
            assert eng.kv.rolling and eng.kv.ring == 24
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, V - 1, n).tolist() for n in (50, 3, 33, 64, 17, 40)]
        want = [_generated(layout, p, 4 + 3 * i) for i, p in enumerate(prompts)]
        for _pass in range(2):
            outs: list = [None] * len(prompts)

            def run(i):
                outs[i] = eng.generate(prompts[i], max_new_tokens=4 + 3 * i)

            threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert outs == want
        _settle(eng)
        assert any(len(r["rows"]) == 2 for r in _steps(eng))  # rows were packed
        if "prefix" in layout:
            assert eng.stats()["kvcache"]["prefix"]["hits"] >= 1
    finally:
        eng.close()


@pytest.mark.parametrize("layout", ["ring", "pool"])
def test_a_request_at_an_idle_warmed_engine_compiles_nothing(layout):
    from gofr_tpu.profiling import default_registry

    eng = _engine(layout, warmup=True, max_seq_len=96)
    try:
        compiles = default_registry().snapshot()["totals"]["compiles"]
        assert len(eng.generate(PROMPT, max_new_tokens=6)) == 6
        _settle(eng)
        assert default_registry().snapshot()["totals"]["compiles"] == compiles
        assert eng.stats()["steps_without_decode"] == 2  # rows 0-15 and 16-31; the last row rides a fused step
    finally:
        eng.close()


def test_the_warm_up_runs_every_decode_free_program():
    """One `_d0` program a (chunk shape, width) pair, as for the fused ones."""
    from gofr_tpu.profiling import default_registry

    eng = _engine("slab", warmup=True, max_seq_len=96, kv_label="warm-d0", prefill_buckets=(8, CHUNK))
    try:
        rows = [r for r in default_registry().snapshot()["programs"] if r.get("model") == "warm-d0"]
        by_name: dict = {}
        for r in rows:
            by_name[r["program"]] = by_name.get(r["program"], 0) + 1
        shapes = eng.stats()["chunk_shapes"]
        assert len(shapes) >= 1
        for s in shapes:
            assert by_name.get(f"llm.step_p{s}_d0") == by_name.get(f"llm.step_p{s}_d{K}") >= 1, by_name
    finally:
        eng.close()


def test_lookahead_bounds_decode_free_steps_in_flight():
    """A slow collector lets the scheduler run ahead: never past `lookahead`
    programs in flight, and up to it, while every step is decode-free."""
    eng = _engine("ring", lookahead=2, max_seq_len=400)
    try:
        process = eng._process_entry

        def slow(entry):
            time.sleep(0.03)
            process(entry)

        eng._process_entry = slow
        prompt = np.random.default_rng(9).integers(1, V - 1, 16 * 12).tolist()
        assert len(eng.generate(prompt, max_new_tokens=4)) == 4
        _settle(eng)
        recs = _steps(eng)
        d0 = [r for r in recs if r["k"] == 0]
        assert len(d0) == 11 and recs[-1]["k"] == K
        assert all(r["depth"] < eng.lookahead for r in recs)  # depth: programs in flight before this one
        assert max(r["depth"] for r in d0) == eng.lookahead - 1
    finally:
        eng.close()
