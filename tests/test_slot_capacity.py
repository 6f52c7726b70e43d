"""What a slot holds against what a request may ask (LLMEngine.submit and
CacheManager's rows rule).

``max_seq_len`` is prompt + output: a request with ``plen + max_new <=
max_seq_len`` streams every token it asked, in every KV layout, and the
engine's own merge slack (two decode chunks) is built into the slot on top,
never taken from the request. The last class holds the benchmark's traffic
to the same rule: no request of a workload's pool is one that ``submit``
would cap, which is what made a faster engine "fail" a request it had in
fact served (PERF.md, PR 26).
"""

import glob
import json
import os
import sys

import jax
import pytest

from gofr_tpu.kvcache import CacheManager
from gofr_tpu.llm import GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, init_params

CFG = TransformerConfig.tiny()
CFGW = TransformerConfig.tiny_mistral()  # sliding window 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# layout -> (config, engine arguments); one slot, so that the paged pool's
# default is exactly one slot's share and the request at the limit has to fit it
LAYOUTS = {
    "paged": (CFG, dict(kv_paged=True, kv_block=4)),
    "dense": (CFG, dict(kv_paged=False)),
    "rolling": (CFGW, dict(kv_paged=False)),
}
MAX_SEQ, PLEN = 128, 30
PROMPT = [(7 * i) % 250 + 1 for i in range(PLEN)]


@pytest.fixture(scope="module", params=sorted(LAYOUTS))
def engines(request):
    """(layout, an engine of max_seq_len 128, one of 256) over one model."""
    cfg, kw = LAYOUTS[request.param]
    params = init_params(jax.random.PRNGKey(1), cfg)
    made = [
        LLMEngine(cfg, params, slots=1, max_seq_len=n, warmup=False, **kw)
        for n in (MAX_SEQ, 2 * MAX_SEQ)
    ]
    assert made[0].kv.stats()["layout"] == request.param
    yield (request.param, *made)
    for e in made:
        e.close()


class TestARequestMayHoldMaxSeqLen:
    def test_at_the_limit_every_token_is_streamed(self, engines):
        """plen + max_new == max_seq_len: exactly max_new tokens, not
        capped, and the tokens a roomier engine gives."""
        _, tight, roomy = engines
        asked = MAX_SEQ - PLEN
        req = tight.submit(GenRequest(PROMPT, max_new_tokens=asked))
        got = req.tokens()
        assert len(got) == asked
        assert req.finish_reason == "length" and not req.capped
        assert got == roomy.generate(PROMPT, max_new_tokens=asked)

    def test_one_token_more_is_capped_and_flagged(self, engines):
        _, tight, _ = engines
        req = tight.submit(GenRequest(PROMPT, max_new_tokens=MAX_SEQ - PLEN + 1))
        assert req.capped and req.max_new_tokens == MAX_SEQ - PLEN
        assert len(req.tokens()) == MAX_SEQ - PLEN
        assert req.finish_reason == "length"

    @pytest.mark.parametrize("plen", [MAX_SEQ, MAX_SEQ + 1])
    def test_a_prompt_of_max_seq_len_is_refused(self, engines, plen):
        _, tight, _ = engines
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            tight.submit(GenRequest([1] * plen, max_new_tokens=1))

    def test_a_continuation_is_capped_by_what_remains(self, engines):
        """The failover arithmetic: history folded into the prompt, only
        the tokens still to come need room."""
        _, tight, _ = engines
        req = GenRequest(PROMPT + [5] * 10, max_new_tokens=MAX_SEQ - PLEN)
        req.emitted = 10
        tight.submit(req)
        assert not req.capped and req.max_new_tokens == MAX_SEQ - PLEN
        assert len(req.tokens()) == MAX_SEQ - PLEN - 10

    def test_the_slot_holds_the_merge_slack_on_top(self, engines):
        layout, tight, _ = engines
        kv = tight.kv
        assert kv.slot_rows == MAX_SEQ + 2 * tight.decode_chunk
        if layout == "rolling":  # the ring never clamps: window + slack, as before
            assert kv.capacity == CFGW.sliding_window + kv.append_slack
        else:
            assert kv.capacity == 256  # 144 rows, in whole flash key blocks


class TestRowsRule:
    """CacheManager: slot_rows = max_seq_len + 2 chunks; a flat slot is built
    with them, in whole 128-key blocks where max_seq_len was; the default
    pool counts what a request can fill, not the rounded width."""

    # max_seq_len, chunk, block -> capacity, table width, default blocks a slot
    @pytest.mark.parametrize(
        "max_seq_len,chunk,block,capacity,width,share",
        [
            (1792, 8, 16, 1920, 120, 113),  # qwen2-7b.reason-closed
            (1792, 8, 64, 1920, 30, 29),
            (1792, 8, 128, 1920, 15, 15),
            (2048, 16, 16, 2176, 136, 130),
            (128, 8, 16, 256, 16, 9),
            (64, 8, 4, 80, 20, 20),  # no alignment to keep
            (100, 8, 16, 128, 8, 8),  # the block's own rounding only
        ],
    )
    def test_paged(self, max_seq_len, chunk, block, capacity, width, share):
        slots = 3
        kv = CacheManager(CFG, slots, max_seq_len, chunk, paged=True, block=block)
        assert kv.slot_rows == max_seq_len + 2 * chunk
        assert (kv.capacity, kv.table_width) == (capacity, width)
        assert kv.pool.n_blocks == slots * share
        if max_seq_len % 128 == 0:
            assert kv.capacity % 128 == 0  # the flash kernel's key blocks

    @pytest.mark.parametrize(
        "max_seq_len,chunk,capacity",
        [(1792, 8, 1920), (512, 8, 640), (512, 16, 640), (64, 8, 80), (100, 8, 116)],
    )
    def test_dense(self, max_seq_len, chunk, capacity):
        kv = CacheManager(CFG, 2, max_seq_len, chunk, paged=False)
        assert not kv.rolling and kv.capacity == capacity

    @pytest.mark.parametrize("widths", [(8,), (8, 16, 64)])
    def test_rolling_is_window_plus_slack_as_before(self, widths):
        kv = CacheManager(CFGW, 2, 1792, 8, append_widths=widths)
        assert kv.rolling and kv.capacity == CFGW.sliding_window + max(widths)

    @pytest.mark.parametrize("widths", [(8,), (8, 16, 64), (8, 256)])
    def test_a_request_at_the_limit_reserves_no_more_than_its_share(self, widths):
        """Whatever the append widths, the worst case of a request that
        fills max_seq_len fits one slot's share of the default pool."""
        kv = CacheManager(CFG, 1, 1792, 8, paged=True, block=16, append_widths=widths)
        rows = kv.reserve_tokens(512, 1280)
        assert 512 + 1280 - 1 + 8 <= rows <= kv.slot_rows  # the last chunk's merge fits
        assert kv.blocks_for(rows) <= kv.pool.n_blocks == 113


# -- the benchmark's traffic against its engines --------------------------------

WORKLOADS = sorted(
    os.path.basename(p)[: -len(".json")]
    for p in glob.glob(os.path.join(REPO, "benchmarks", "workloads", "*.json"))
)


def _pool_requests(workload: dict):
    """Every distinct request a run of the workload can send."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import traffic
    finally:
        sys.path.pop(0)
    plan = traffic.Plan(workload, 0, 512)
    if plan.kind == "closed":
        return [
            plan.client_request(c, k)
            for k in range(1, plan.pool * plan.turns + 1) for c in range(plan.clients)
        ]
    return plan.arrivals(float(plan.gaps.sum()) * (plan.turns + 1))


@pytest.mark.parametrize("twin", ["cell", "rehearsal"])
@pytest.mark.parametrize("name", WORKLOADS)
def test_no_benchmark_request_is_one_submit_would_cap(name, twin):
    """benchmarks/load.py fails a request that returns another count than
    it asked, so a pool request that the engine of its configuration caps
    turns `correct` false as soon as the engine is fast enough to finish
    it inside a window. Each request goes through a real submit (a tiny
    model under the configuration's max_seq_len) and is cancelled."""
    with open(os.path.join(REPO, "benchmarks", "workloads", f"{name}.json")) as f:
        workload = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs", f"{workload['config']}.json")) as f:
        config = json.load(f)
    if twin == "rehearsal":
        workload, config = {**workload, **workload["rehearsal"]}, config["rehearsal"]
    distinct: dict = {}
    for spec in _pool_requests(workload):
        distinct.setdefault((spec.prompt_len, spec.output_len), spec)
    requests = list(distinct.values())
    assert len(requests) >= min(workload["pool"], 8)
    eng = LLMEngine(
        CFG, init_params(jax.random.PRNGKey(2), CFG), slots=1,
        max_seq_len=int(config["engine"]["max_seq_len"]), warmup=False,
    )
    try:
        capped = []
        for spec in requests:
            req = GenRequest([1] * spec.prompt_len, max_new_tokens=spec.output_len)
            eng.submit(req)
            req.cancel()
            if req.capped or req.max_new_tokens != spec.output_len:
                capped.append((spec.client, spec.index, spec.prompt_len, spec.output_len))
        assert not capped, f"(client, k, prompt, asked) the engine would cut short: {capped}"
    finally:
        eng.close()
