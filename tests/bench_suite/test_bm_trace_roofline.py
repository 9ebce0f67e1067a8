"""Trace reduction on hand-made intervals and on a small recorded trace;
FLOP and byte functions against hand counts.  No TPU library."""

import os

import pytest

from benchmarks import harness, layer_lib, roofline, trace_reduce as tr

MISTRAL = harness.load_json(harness.HERE, "configs", "mistral-7b-v0.3.json")
DEEPSEEK = harness.load_json(harness.HERE, "configs", "deepseek-llm-7b.json")
PLANE = {
    "modules": [("jit__decode_fn(11)", 0.0, 1.0), ("jit__prefill_fn(7)", 1.5, 2.0),
                ("jit__decode_fn(11)", 4.0, 1.0)],
    "ops": [("fusion.1", 0.0, 0.4), ("custom-call.3", 0.3, 0.5),
            ("sort.2", 0.9, 0.1), ("fusion.9", 1.5, 2.0),
            ("custom-call.3", 4.0, 0.5), ("fusion.1", 4.6, 0.4)],
}


@pytest.mark.parametrize("raw,want", [
    ("fusion.123", "fusion"), ("jit__decode_fn(987654)", "jit__decode_fn"),
    ("%custom-call.5", "custom-call"), ("sort", "sort"),
    ("fusion.12.3", "fusion"), ("7", "7"),
    ("%_decode_fn.16 = bf16[64,32,128]{2,1,0:T(8,128)(2,1)} custom-call(s32[64,256]"
     "{1,0:T(8,128)} %tables.1), custom_call_target=\"tpu_custom_call\"",
     "custom-call__decode_fn"),
    ("%fusion.5 = (bf16[8]{0:T(8)}, f32[2]{0}) fusion(f32[8]{0} %p.1), kind=kLoop",
     "fusion"),
    ("%convolution_multiply_fusion.3 = bf16[8,128]{1,0} fusion(bf16[8]{0} %a)",
     "fusion_convolution_multiply_fusion"),
    ("%sort.2 = (f32[64,32768]{1,0}, s32[64,32768]{1,0}) sort(f32[64,32768]{1,0} %x)",
     "sort")])
def test_norm(raw, want):
    assert tr.norm(raw) == want


def test_busy_is_the_union_of_intervals():
    assert tr.union_seconds(PLANE["ops"]) == pytest.approx(0.8 + 0.1 + 2.0 + 0.5 + 0.4)
    assert tr.union_seconds([]) == 0.0
    assert tr.union_seconds([("a", 0, 2), ("b", 1, 0.5)]) == pytest.approx(2.0)


def test_gaps_are_attributed_to_the_programs_either_side():
    g = tr.gaps_between_modules(PLANE["modules"])
    assert g == pytest.approx({"jit__decode_fn_-__jit__prefill_fn": 0.5,
                               "jit__prefill_fn_-__jit__decode_fn": 0.5})


def test_ops_sum_per_program_and_reduce():
    by = tr.ops_by_module(PLANE)
    assert by["jit__decode_fn"] == pytest.approx(
        {"fusion": 0.8, "custom-call": 1.0, "sort": 0.1})
    assert by["jit__prefill_fn"] == pytest.approx({"fusion": 2.0})
    red = tr.reduce({"/device:TPU:0": PLANE})
    assert red["window_s"] == pytest.approx(5.0)
    assert red["busy_s"] == pytest.approx(3.8)
    assert red["idle_share"] == pytest.approx(1 - 3.8 / 5.0)
    assert red["modules"]["jit__decode_fn"] == {"count": 2, "seconds": 2.0}
    assert red["launches"] == 3 and red["gap_s"] == pytest.approx(1.0)
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["fusion", pytest.approx(2.8)]
    assert len(bd["idle_gaps"]) == 2
    assert tr.reduce({}) is None


def test_ops_need_not_arrive_in_time_order():
    shuffled = dict(PLANE, ops=sorted(PLANE["ops"]))       # by name
    assert tr.ops_by_module(shuffled) == tr.ops_by_module(PLANE)


def test_two_chips_average():
    red = tr.reduce({"/device:TPU:0": PLANE, "/device:TPU:1": PLANE})
    assert red["chips"] == 2 and red["busy_s"] == pytest.approx(3.8)
    assert red["ops"]["sort"] == pytest.approx(0.1)


def test_recorded_trace_reduces_to_the_recorded_numbers():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    want = harness.load_json(harness.HERE, "data", "small_trace.expected.json")
    red = tr.reduce(tr.load(path))
    assert red["chips"] == want["chips"]
    for key in ("window_s", "busy_s", "gap_s", "launches"):
        assert red[key] == pytest.approx(want[key], rel=1e-9)
    assert {k: v["count"] for k, v in red["modules"].items()} == want["module_counts"]
    assert 0.0 < red["idle_share"] < 1.0


@pytest.mark.parametrize("cfg,layer_params,weights_gb,kv_bytes", [
    (MISTRAL, 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336, 7.52, 65536),
    (DEEPSEEK, 4 * 4096 * 4096 + 3 * 4096 * 11008, 7.75, 245760)],
    ids=["mistral", "deepseek"])
def test_sizes_against_hand_counts(cfg, layer_params, weights_gb, kv_bytes):
    assert roofline.layer_matmul_params(cfg) == layer_params
    assert roofline.weight_bytes(cfg) / 1e9 == pytest.approx(weights_gb, abs=0.01)
    assert roofline.kv_bytes_per_token(cfg) == kv_bytes
    assert roofline.decode_kv_bytes(cfg, 1000) == 1000 * kv_bytes


@pytest.mark.parametrize("cfg", [MISTRAL, DEEPSEEK], ids=["mistral", "deepseek"])
def test_prefill_flops_hand_count(cfg):
    n, L = 2048, cfg["num_hidden_layers"]
    want = (2 * roofline.layer_matmul_params(cfg) * L * n
            + 2 * 2 * (n * n / 2) * 32 * 128 * L
            + 2 * 4096 * cfg["vocab_size"])
    assert roofline.prefill_flops(cfg, [n]) == pytest.approx(want)
    assert roofline.prefill_flops(cfg, [100, 200]) == pytest.approx(
        roofline.prefill_flops(cfg, [100]) + roofline.prefill_flops(cfg, [200]))


def test_peaks_table_miss_is_an_error():
    assert roofline.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert roofline.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(LookupError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(LookupError):
        roofline.peaks("cpu")


def test_readers_on_hand_counters():
    red = tr.reduce({"/device:TPU:0": PLANE})
    c = {"model": MISTRAL, "engine": MISTRAL["engine"],
         "peaks": roofline.peaks("TPU v5 lite"),
         "traced": {"probe": {"decode_kv_tokens": 2_000_000,
                              "prefill_launches": 1, "prefill_tokens": 2048,
                              "prefill_tokens_sq": 2048 ** 2}},
         "window": {"probe": {"decode_rows": 30, "decode_launches": 4},
                    "programs": {"decode|4x8": [4, 30, 32],
                                 "prefill|64": [1, 40, 64]}}}
    need_s = 2_000_000 * 65536 / 819e9
    assert layer_lib.paged_decode_roofline(c, red) == pytest.approx(100 * need_s / 1.0)
    assert layer_lib.prefill_flops_share(c, red) == pytest.approx(
        100 * roofline.prefill_flops(MISTRAL, [2048]) / (2.0 * 197e12))
    assert layer_lib.rows_per_step(c) == 7.5
    assert layer_lib.padding_share(c) == pytest.approx(100 * 26 / 96)
    assert layer_lib.host_ms_per_step(red) == pytest.approx(1000 / 3)
    assert layer_lib.module_ms(red, layer_lib.DECODE) == pytest.approx(1000.0)
    assert layer_lib.op_share(red, "sort") == pytest.approx(100 * 0.1 / 3.8)
    assert layer_lib.idle_share(red) == pytest.approx(24.0)
    # nothing to read: nothing returned, and the harness leaves it out
    assert layer_lib.paged_decode_roofline(c, None) is None
    assert layer_lib.prefill_flops_share(c, None) is None
    assert layer_lib.idle_share(None) is None
