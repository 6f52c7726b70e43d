"""Grammar-constrained decoding (gofr_tpu.structured +
docs/advanced-guide/structured-decoding.md).

The load-bearing invariant: a constrained generation is valid under its
schema BY CONSTRUCTION — greedy or sampled, speculative on or off, any
KV layout — because every sampling site masks to what the token DFA
admits and the per-slot state advances inside the fused programs.
Unconstrained neighbors in the same batch must stay token-identical to
an unconstrained-only engine (the mixing contract), and constrained
spec-on must equal constrained spec-off token-for-token.

Host-compiler units run model-free; engine tests use the same tiny
CPU-backend shapes as the rest of the serving suites."""

import json
import time

import jax
import numpy as np
import pytest

from gofr_tpu.llm import EngineOverloaded, GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, init_params
from gofr_tpu.structured import (
    JsonSchemaError,
    compile_json_schema,
    grammar_cache,
    vocab_from_tokenizer,
)

CFG = TransformerConfig.tiny(vocab_size=128)

# char-level vocabulary: id i -> printable byte, last id = eos
VOCAB = [
    chr(0x20 + i).encode() if 0x20 + i < 0x7F else b"" for i in range(127)
] + [b""]
EOS = 127

SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "maxLength": 6},
        "n": {"type": "integer"},
    },
}


def _text(toks: list[int]) -> str:
    return b"".join(VOCAB[t] for t in toks if t != EOS).decode()


def _validate(obj, schema) -> None:
    import jsonschema

    jsonschema.validate(obj, schema)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def grammar():
    return compile_json_schema(SCHEMA, VOCAB, EOS)


def _engine(params, **kw) -> LLMEngine:
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq_len", 160)
    kw.setdefault("warmup", False)
    return LLMEngine(CFG, params, **kw)


# ---------------------------------------------------------------------------
# host compiler
# ---------------------------------------------------------------------------

class TestCompiler:
    def test_random_walks_always_valid(self, grammar):
        # any path that only takes admitted tokens and ends at eos is a
        # valid document — the by-construction guarantee, model-free
        import random

        rng = random.Random(7)
        completed = 0
        for _ in range(100):
            s, out = grammar.start, []
            for _ in range(300):
                allowed = np.where(grammar.allowed(s))[0]
                assert len(allowed), "live state with empty mask"
                t = int(rng.choice(allowed))
                nxt = grammar.advance(s, t)
                if t == EOS:
                    break
                out.append(t)
                s = nxt
            else:
                continue
            _validate(json.loads(_text(out)), SCHEMA)
            completed += 1
        assert completed >= 50  # the walk budget completes most docs

    def test_shapes_compile_and_walk(self):
        cases = [
            {"enum": ["a", "b c", 3]},
            {"const": {"k": [1, 2]}},
            {"type": "array", "items": {"type": "integer"},
             "minItems": 1, "maxItems": 3},
            {"type": "boolean"},
            {"type": "null"},
            {"anyOf": [{"type": "integer"}, {"type": "null"}]},
            {"type": "object", "properties": {
                "inner": {"type": "object", "properties": {
                    "x": {"type": "number"}}},
            }},
            {"type": ["integer", "null"]},
        ]
        for schema in cases:
            g = compile_json_schema(schema, VOCAB, EOS)
            # greedy-min walk: always take the smallest admitted token
            s, out = g.start, []
            for _ in range(300):
                allowed = np.where(g.allowed(s))[0]
                assert len(allowed), f"empty mask for {schema}"
                t = int(allowed[0])
                if t == EOS:
                    break
                out.append(t)
                s = g.advance(s, t)
            else:
                pytest.fail(f"walk did not terminate for {schema}")
            _validate(json.loads(_text(out)), schema)

    def test_multi_char_tokens(self):
        vocab = [b'{"a":', b"1", b"23", b"}", b"x", b'{"a"', b":", b""]
        g = compile_json_schema(
            {"type": "object", "properties": {"a": {"type": "integer"}}},
            vocab, len(vocab) - 1, whitespace=False,
        )
        # multi-byte tokens advance the byte DFA atomically
        s = g.advance(g.start, 0)  # {"a":
        assert s >= 0
        s2 = g.advance(s, 2)  # 23
        assert s2 >= 0
        assert g.advance(s2, 3) >= 0  # }
        assert g.advance(s, 4) < 0  # "x" not admitted in an integer

    def test_filter_draft_cuts_at_first_illegal(self, grammar):
        # draft '{"n' ... then an illegal token
        ids = [VOCAB.index(c.encode()) for c in '{"']
        bad = VOCAB.index(b"}")
        kept = grammar.filter_draft(grammar.start, ids + [bad] + ids)
        assert kept == ids

    def test_unsupported_schema_raises_400(self):
        with pytest.raises(JsonSchemaError) as ei:
            compile_json_schema({"type": "wat"}, VOCAB, EOS)
        assert getattr(ei.value, "status_code", None) == 400

    def test_vocabulary_cannot_realize(self):
        # digits missing from the vocabulary -> integers impossible
        vocab = [b"a", b"b", b"{", b"}", b'"', b":", b""]
        with pytest.raises(JsonSchemaError):
            compile_json_schema({"type": "integer"}, vocab, len(vocab) - 1)

    def test_nesting_bound(self):
        schema: dict = {"type": "integer"}
        for _ in range(20):
            schema = {"type": "object", "properties": {"x": schema}}
        with pytest.raises(JsonSchemaError):
            compile_json_schema(schema, VOCAB, EOS)

    def test_grammar_cache_dedups(self):
        grammar_cache.clear()
        g1 = grammar_cache.get(SCHEMA, VOCAB, EOS)
        g2 = grammar_cache.get(dict(SCHEMA), VOCAB, EOS)
        assert g1 is g2

    def test_vocab_from_tokenizer_bytes(self):
        from gofr_tpu.models.tokenizer import ByteTokenizer

        v = vocab_from_tokenizer(ByteTokenizer(300))
        assert len(v) == 300
        assert v[65] == b"A"
        assert v[256] == b"" and v[299] == b""

    def test_mask_prep_cost_bounded(self):
        # the host cost constrained serving pays per NEW schema: compile
        # + one advance per emitted token. Bounded here so a regression
        # to exponential subset construction fails loudly.
        t0 = time.perf_counter()
        g = compile_json_schema(SCHEMA, VOCAB, EOS, max_states=4096)
        compile_s = time.perf_counter() - t0
        assert compile_s < 5.0
        t0 = time.perf_counter()
        s = g.start
        for _ in range(10_000):
            allowed = np.where(g.allowed(s))[0]
            if not len(allowed):  # done/dead: restart the walk
                s = g.start
                continue
            s2 = g.advance(s, int(allowed[0]))
            s = s2 if 0 <= s2 < g.n_states else g.start
        assert time.perf_counter() - t0 < 2.0


# ---------------------------------------------------------------------------
# engine guarantees
# ---------------------------------------------------------------------------

class TestEngineConstrained:
    @pytest.mark.parametrize("layout", ["paged", "dense"])
    def test_greedy_valid_across_layouts(self, params, grammar, layout):
        eng = _engine(params, kv_paged=(layout == "paged"))
        try:
            outs = [
                eng.submit(GenRequest(
                    [1 + i, 2, 3], max_new_tokens=100, grammar=grammar,
                )) for i in range(3)
            ]
            for r in outs:
                toks = r.tokens(timeout=120)
                assert r.finish_reason == "eos"
                _validate(json.loads(_text(toks)), SCHEMA)
        finally:
            eng.close()

    def test_windowed_rolling_layout(self, params, grammar):
        cfg = TransformerConfig.tiny_mistral(vocab_size=128)
        p = init_params(jax.random.PRNGKey(0), cfg)
        eng = LLMEngine(cfg, p, slots=2, max_seq_len=160, warmup=False)
        try:
            assert eng.kv.ring > 0  # sliding-window model -> rolling ring
            r = eng.submit(GenRequest(
                [1, 2, 3], max_new_tokens=100, grammar=grammar,
            ))
            toks = r.tokens(timeout=120)
            assert r.finish_reason == "eos"
            _validate(json.loads(_text(toks)), SCHEMA)
        finally:
            eng.close()

    def test_a_routed_models_grammar_programs_count_their_experts(self, grammar):
        """tiny_moe, a plain request, then one constrained and one plain together: the
        grammar programs return the expert vector their plain twins return
        (the parent's constrained paged copies had not been given moe_out),
        so stats()["moe"] counts the layer calls of EVERY dispatched program."""
        cfg = TransformerConfig.tiny_moe(vocab_size=128)
        eng = LLMEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), slots=2,
                        max_seq_len=160, warmup=False)
        try:
            assert eng.kv.paged
            assert len(eng.generate([7, 8, 9], max_new_tokens=8)) == 8  # no grammar resident: plain programs
            held = eng.submit(GenRequest([1, 2, 3], max_new_tokens=100, grammar=grammar))
            free = eng.submit(GenRequest([4, 5, 6], max_new_tokens=24))
            _validate(json.loads(_text(held.tokens(timeout=120))), SCHEMA)
            assert len(free.tokens(timeout=120)) == 24
            time.sleep(0.2)  # tail chunks of finished requests close their records
            st = eng.stats()
            records = [dict(zip(st["step_log"]["fields"], r)) for r in st["step_log"]["records"]]
            with_grammar = [r for r in records if r["program"].endswith("g")]
            assert with_grammar and len(with_grammar) < len(records)
            assert all(r["moe_pairs"] > 0 and r["moe_touched"] > 0 for r in records)
            assert st["moe"]["layer_calls"] == cfg.n_layers * sum(
                r["k"] + (r["kind"] == "step") for r in records
            )
        finally:
            eng.close()

    def test_sampled_outputs_all_valid(self, params, grammar):
        eng = _engine(params)
        try:
            for seed in range(4):
                r = eng.submit(GenRequest(
                    [5 + seed, 9], max_new_tokens=110,
                    temperature=0.9, grammar=grammar,
                ))
                toks = r.tokens(timeout=120)
                assert r.finish_reason == "eos"
                _validate(json.loads(_text(toks)), SCHEMA)
        finally:
            eng.close()

    def test_spec_on_token_identical_to_spec_off(self, params, grammar):
        base = _engine(params)
        try:
            want = base.submit(GenRequest(
                [3, 1, 4], max_new_tokens=100, grammar=grammar,
            )).tokens(timeout=120)
        finally:
            base.close()
        spec = _engine(params, speculative=True, spec_draft=4)
        try:
            got_r = spec.submit(GenRequest(
                [3, 1, 4], max_new_tokens=100, grammar=grammar,
            ))
            got = got_r.tokens(timeout=120)
            assert got == want
            _validate(json.loads(_text(got)), SCHEMA)
            # the drafter proposed through the grammar filter: whatever
            # it proposed was DFA-admissible, and acceptance telemetry
            # lands in the constrained split
            s = spec._spec_summary()
            assert s["constrained"]["proposed"] == spec.spec_proposed
        finally:
            spec.close()

    def test_unconstrained_neighbor_token_identical(self, params, grammar):
        solo = _engine(params)
        try:
            want = solo.submit(
                GenRequest([7, 8, 9], max_new_tokens=12)
            ).tokens(timeout=60)
        finally:
            solo.close()
        mixed = _engine(params)
        try:
            rc = mixed.submit(GenRequest(
                [1, 2, 3], max_new_tokens=100, grammar=grammar,
            ))
            ru = mixed.submit(GenRequest([7, 8, 9], max_new_tokens=12))
            got_u = ru.tokens(timeout=60)
            got_c = rc.tokens(timeout=120)
            assert got_u == want
            _validate(json.loads(_text(got_c)), SCHEMA)
        finally:
            mixed.close()

    def test_preempted_constrained_stream_still_valid(self, params, grammar):
        # a batch-class constrained request preempted for interactive
        # work re-admits as a continuation: the grammar state re-seeds
        # from the host mirror, so the final document is still valid
        eng = _engine(params, slots=1, preemption=True)
        try:
            # the race is real: a fast (warm-cache) decode can close the
            # grammar before the interactive submit lands its preemption
            # — retry until a round actually preempts; every round's
            # document must be valid either way
            for _ in range(10):
                rc = eng.submit(GenRequest(
                    [1, 2, 3], max_new_tokens=100, grammar=grammar,
                    priority="batch",
                ))
                while rc.emitted < 1 and rc.finish_reason is None:
                    time.sleep(0.002)  # let it get mid-stream
                ri = eng.submit(GenRequest([9, 9], max_new_tokens=4))
                ri.tokens(timeout=60)
                toks = rc.tokens(timeout=180)
                assert rc.finish_reason == "eos"
                _validate(json.loads(_text(toks)), SCHEMA)
                if rc.preempted >= 1:
                    break
            assert rc.preempted >= 1
        finally:
            eng.close()

    def test_eos_mismatch_rejected(self, params, grammar):
        eng = _engine(params)
        try:
            with pytest.raises(ValueError, match="eos"):
                eng.submit(GenRequest(
                    [1, 2], max_new_tokens=8, grammar=grammar, eos_token=3,
                ))
            # unset eos adopts the grammar's
            r = eng.submit(GenRequest(
                [1, 2], max_new_tokens=100, grammar=grammar,
            ))
            r.tokens(timeout=120)
            assert r.eos_token == EOS
        finally:
            eng.close()

    def test_wave_scheduler_rejects_grammar(self, params, grammar):
        eng = _engine(params, step_token_budget=0)
        try:
            assert not eng.constrained
            with pytest.raises(ValueError, match="chunked"):
                eng.submit(GenRequest([1], max_new_tokens=8, grammar=grammar))
        finally:
            eng.close()

    def test_vocab_mismatch_rejected(self, params):
        small = compile_json_schema(
            {"type": "boolean"}, [b"true", b"false", b""], 2
        )
        eng = _engine(params)
        try:
            with pytest.raises(ValueError, match="vocab"):
                eng.submit(GenRequest([1], max_new_tokens=8, grammar=small))
        finally:
            eng.close()

    def test_grammar_slots_evict_and_overflow(self, params, grammar):
        eng = _engine(params, constrained_grammars=2)
        try:
            boolean = compile_json_schema({"type": "boolean"}, VOCAB, EOS)
            r1 = eng.submit(GenRequest(
                [1, 2], max_new_tokens=100, grammar=grammar,
            ))
            r1.tokens(timeout=120)
            r2 = eng.submit(GenRequest(
                [1, 2], max_new_tokens=20, grammar=boolean,
            ))
            r2.tokens(timeout=120)
            # both resident; a third DISTINCT grammar evicts a zero-ref slot
            null_g = compile_json_schema({"type": "null"}, VOCAB, EOS)
            r3 = eng.submit(GenRequest(
                [1, 2], max_new_tokens=20, grammar=null_g,
            ))
            assert _text(r3.tokens(timeout=120)) == "null"
            assert eng._constrained_summary()["grammars_resident"] == 2
        finally:
            eng.close()

    def test_constrained_metrics_and_zeroing(self, params, grammar):
        from gofr_tpu.metrics import Manager

        m = Manager()
        eng = _engine(params, metrics=m)
        try:
            r = eng.submit(GenRequest(
                [1, 2], max_new_tokens=100, grammar=grammar,
            ))
            r.tokens(timeout=120)
            text = m.render_prometheus()
            assert "app_llm_constrained_requests_total" in text
            assert 'app_llm_constrained_grammars{model="llm"} 1' in text
        finally:
            eng.close()
        # dead-engine gauge regression class: close() zeroes the gauge
        assert 'app_llm_constrained_grammars{model="llm"} 0' in (
            m.render_prometheus()
        )

    def test_stats_block(self, params, grammar):
        eng = _engine(params)
        try:
            eng.submit(GenRequest(
                [1, 2], max_new_tokens=100, grammar=grammar,
            )).tokens(timeout=120)
            st = eng.stats()["constrained"]
            assert st["enabled"] and st["requests"] == 1
            assert st["grammars_resident"] == 1
        finally:
            eng.close()
