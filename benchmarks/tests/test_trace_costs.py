"""The trace reduction on a hand-built ProfileData-shaped fixture, and the
byte/FLOP functions against numbers worked by hand."""
import types

import costs
import pytest
import trace as T
import traffic

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return types.SimpleNamespace(name=name, start_ns=int(start_ms * MS), duration_ns=int(dur_ms * MS))


def fixture():
    ops = types.SimpleNamespace(name="XLA Ops", events=[
        ev("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p)", 0, 4), ev("paged_decode_kernel", 3, 3),  # overlap: union 0..6
        ev("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p)", 10, 2),  # gap 6..10
        ev("paged_decode_kernel", 12, 1), ev("copy.3", 20, 5),  # gap 13..20
        ev("%while.7 = (s32[]) while((s32[]) %t), body=%b", 0, 13),  # a loop spans its body and the gap in it
    ])
    mods = types.SimpleNamespace(name="XLA Modules", events=[
        ev("jit__step(123)", 0, 6), ev("jit__chunk(456)", 10, 3), ev("jit__chunk(456)", 20, 5),
    ])
    other = types.SimpleNamespace(name="Steps", events=[ev("0", 0, 25)])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[ops, mods, other])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=[ev("x", 0, 100)])])
    return types.SimpleNamespace(planes=[host, dev])


def test_union_with_overlap_and_gaps():
    busy, gaps = T.union_ns([(0, 4), (3, 6), (10, 12), (12, 13), (20, 25)])
    assert busy == 6 + 3 + 5
    assert gaps == [(6, 4), (13, 7)]


def test_reduce_counts_programs_ops_and_idle():
    red = T.reduce_planes(fixture())
    assert len(red["devices"]) == 1
    # the loop's own event is no operation: the gap 6..10 inside it stays a gap
    assert red["window_s"] == red["span_s"] == pytest.approx(0.025) and red["busy_s"] == pytest.approx(0.014)
    # the host traced 40 ms: what the device did not fill at the edges is idle too
    wide = T.reduce_planes(fixture(), traced_s=0.040)
    assert wide["window_s"] == pytest.approx(0.040) and wide["span_s"] == pytest.approx(0.025)
    assert T.reduce_planes(fixture(), traced_s=0.010)["window_s"] == pytest.approx(0.025)
    assert T.op_seconds(red, r"paged_decode") == (pytest.approx(0.004), 2)
    assert T.module_runs(red, r"^jit__chunk") == [pytest.approx(0.003), pytest.approx(0.005)]
    assert T.module_runs(red, r"^jit__step") == [pytest.approx(0.006)]
    assert T.module_runs(red, r"^nothing") == []
    b = T.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1 fusion", pytest.approx(0.006)]
    assert not any(name.startswith("while") for name, _ in b["device_ops"])
    assert b["idle_gaps"][0][0] == "unattributed (after jit__chunk, before jit__chunk)"
    assert b["idle_gaps"][0][1] == pytest.approx(0.007)


def test_no_device_plane_is_an_error():
    data = fixture()
    data.planes = data.planes[:1]
    with pytest.raises(ValueError):
        T.reduce_planes(data)


def model(name):
    return traffic.load(f"{costs.HERE}/configs/{name}.json")["model"]


def test_qwen2_by_hand():
    m = model("qwen2-7b")
    # per layer: 3584*3584 (q) + 3584*1024 (k|v) + 3584*3584 (o) + 3*3584*18944 (mlp)
    assert costs.layer_matmul_params(m) == 12845056 + 3670016 + 12845056 + 203685888 == 233046016
    assert costs.body_matmul_flops_per_token(m) == 2 * 233046016 * 28 == 13050576896
    assert costs.head_flops_per_logit_row(m) == 2 * 3584 * 152064
    assert costs.kv_bytes_per_token(m) == 57344  # 2 * 28 * 4 * 128 * 2
    assert costs.decode_kv_read_bytes(m, [700, 1000]) == 1700 * 57344
    assert costs.attention_flops(m, 1000) == 4 * 28 * 128 * 1000 * 28
    assert costs.weight_bytes(m) == 233046016 * 28 + 2 * 152064 * 3584 == 7615283200


def test_mistral_window_cap_and_the_int8_split():
    m = model("mistral-7b")
    assert costs.layer_matmul_params(m) == 4096 * 4096 * 2 + 4096 * 2048 + 3 * 4096 * 14336 == 218103808
    assert costs.kv_bytes_per_token(m) == 131072
    # past the window a token sees 4096 keys, however long its context
    assert costs.attention_flops(m, 6000) == costs.attention_flops(m, 4096) == 4 * 32 * 128 * 4096 * 32
    assert costs.decode_kv_read_bytes(m, [6000, 100]) == (4096 + 100) * 131072
    pk = {"bf16_flops": 200e12, "int8_ops": 400e12}
    body = 2 * 218103808 * 32
    least = costs.least_step_seconds(m, pk, prefill_contexts=[1, 2], decode_contexts=[5000])
    assert least["prefill_matmul_flops"] == 2 * body
    assert least["decode_matmul_flops"] == body + 2 * 4096 * 32000
    attn = 4 * 32 * 128 * 32 * (1 + 2 + 4096)
    assert least["attention_flops"] == attn
    assert least["seconds"] == pytest.approx(2 * body / 400e12 + (body + 2 * 4096 * 32000 + attn) / 200e12)
    bf16 = costs.least_step_seconds(m, pk, prefill_contexts=[1, 2], decode_contexts=[], prefill_int8=False)
    assert bf16["seconds"] == pytest.approx((2 * body + 4 * 32 * 128 * 32 * 3) / 200e12)


def test_peaks_have_no_default():
    assert costs.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
    with pytest.raises(KeyError):
        costs.peaks("_source")
