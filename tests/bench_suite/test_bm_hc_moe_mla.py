"""The multi-stream latent-attention / routed-expert configuration's
benchmark files: the configuration against the catalog's published keys,
the counts of bytes and operations against hand arithmetic, the traffic
mix, the six readers on one synthetic trace (and silent on a recorded
trace of another model), builder and reference at a tiny size, and a tiny
cell end to end through the launcher on the CPU.  No TPU library.

The checks of the benchmark's entries are ``check_*(bench)`` functions
(the append contract at the head of ``test_bm_harness.py``): no pin on
last place, no count over a list."""

import io
import json
import os
import shutil

import pytest

from benchmarks import (harness, hc_moe_mla_spans as spans,
                        roofline_hc_moe_mla as rf)
from benchmarks.traffic_kinds import backlog

XING = harness.load_json(harness.HERE, "configs", "xing4.0-29b-a4b.json")
MIX = harness.load_json(harness.HERE, "traffic", "doc-prefill.json")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = "xing4.0-29b-a4b"
CELL = "xing4.0-29b-a4b.doc-prefill"
OWN = ("programs.mhc_share", "kernels.mhc_roofline",
       "kernels.mla_prefill_roofline", "kernels.moe_prefill_experts_roofline",
       "programs.moe_prefill_overhead_share", "engine.hc_clamped_share")
# the catalog's ``config`` of the row ``Xing4.0-29B-A4B`` (model-configs
# guide, ``architectures.jsonl``), as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
SERVED = {"num_hidden_layers": 6, "first_k_dense_replace": 1,
          "max_position_embeddings": 8192, "num_nextn_predict_layers": 0}
TINY = {"source": "test", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 128, "moe_intermediate_size": 48,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "rope_scaling": {"type": "yarn", "factor": 8.0, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32},
        "tie_word_embeddings": False, "q_lora_rank": 32, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 20,
        "n_routed_experts": 8, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 2,
        "routed_scaling_factor": 2.0, "norm_topk_prob": True,
        # 4 rounds, not 20: the step programs of the tiny cell compile faster
        "first_k_dense_replace": 1, "hc_mult": 4, "hc_sinkhorn_iters": 4,
        "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "reduced": {},
        "builder": "hc_moe_mla", "reference": "hc_moe_mla_decoder",
        "engine": {"num_blocks": 64, "block_size": 16,
                   "pool_dtype": "bfloat16", "max_num_seqs": 8,
                   "max_queue": 64, "prefix_cache": False},
        # bf16 at toy widths: scores of 8 experts crowd together, so most
        # rows have a near-tie somewhere; the plumbing is what this checks
        "check": {"prompt_lens": [12, 7], "decode_steps": 2, "atol": 0.05,
                  "rms_rel": 0.08, "margin_eps": 0.004,
                  "max_left_out_share": 0.9}}


# --- the configuration file and the benchmark's entries ------------------------------

def test_every_published_key_is_unchanged_but_those_under_reduced():
    assert sorted(XING["reduced"]) == sorted(SERVED)
    for key, value in PUBLISHED.items():
        assert XING[key] == SERVED.get(key, value), key
    assert XING["source"].startswith(
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B")
    for key in ("hc_norm", "hc_sinkhorn_order", "hc_clip", "hc_entry_exit",
                "hc_precision", "hc_draws", "rope_pairing", "softmax_scale",
                "n_group", "num_nextn_predict_layers"):
        assert XING["assumed"][key]
    assert XING["deployment"] and XING["check"]["why"]
    eng = XING["engine"]
    assert (eng["num_blocks"], eng["block_size"], eng["pool_dtype"],
            eng["prefix_cache"]) == (16384, 16, "bfloat16", False)
    lens = XING["check"]["prompt_lens"]
    assert all(MIX["prompt_len"]["min"] <= n <= MIX["prompt_len"]["max"]
               for n in lens) and XING["check"]["decode_steps"] >= 4
    check_config_entry(BENCH)


def check_config_entry(bench):
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert sorted(entry["reduced"]) == sorted(XING["reduced"])
    assert entry["file"] == "benchmarks/configs/xing4.0-29b-a4b.json"
    assert entry["source"] == XING["source"]
    names = [c["name"] for c in bench["configs"]]
    # appended: after every configuration that was accepted before it
    assert names.index(CONFIG) > names.index("command-a-plus-05-2026")


def check_cell_entries(bench):
    row = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (row["config"], row["traffic"], row["chips"]) == \
        (CONFIG, "doc-prefill", 1)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index(
        "command-a-plus-05-2026.doc-reasoning-decode")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(OWN) <= listed
    assert {"scheduler.padding_share", "cache.pool_peak_share",
            "cache.preemptions", "device.peak_hbm_gb",
            "programs.warm_s_per_program", "programs.attn_share.batch",
            "programs.mlp_share.batch", "programs.lm_head_share.batch",
            "device.idle_share.batch",
            "programs.compiles_in_window.batch"} <= listed
    # not the dense counts, nor what other cells' tests hold to themselves
    assert not {"kernels.paged_decode_roofline",
                "programs.prefill_flops_share", "kernels.mla_decode_roofline",
                "kernels.moe_experts_roofline", "programs.moe_overhead_share",
                "engine.moe_load_max_over_mean"} & listed
    e2e = {m["name"] for m in harness.Cell(CELL, bench=bench).end_to_end}
    assert e2e == {"tokens_per_s", "setup_s"}
    # in every list it shares it stands after the cells accepted before it
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads", ())
        if CELL in ws and len(ws) > 1:
            assert all(ws.index(CELL) > ws.index(w) for w in ws
                       if w in cells[:cells.index(CELL)])


def check_the_six_entries(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in OWN]
    assert at == sorted(at)                 # their order among themselves
    assert at[0] > names.index("frontdoor.itl_p998_ms")     # after PR 42's
    for n in OWN:
        m = bench["per_layer"][names.index(n)]
        assert m["moves"] == "tokens_per_s" and m["workloads"] == [CELL]
    by = {n: bench["per_layer"][names.index(n)] for n in OWN}
    assert [by[n]["better"] for n in OWN] == [
        "lower", "higher", "higher", "higher", "lower", "lower"]
    assert by["engine.hc_clamped_share"]["source"] == "program_span"
    assert all(by[n]["unit"] == "%" for n in OWN[:5])


def test_the_cell_and_its_entries_are_appended():
    check_cell_entries(BENCH)
    check_the_six_entries(BENCH)


# --- bytes and operations against the arithmetic of ISSUE 43 ---------------------------

def test_counts_at_the_served_sizes():
    assert rf.attention_params(XING) == 28_411_136 == (
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584 + 1280)
    assert rf.expert_params(XING) == 11_010_048
    assert rf.expert_bytes(XING) == 22_020_096
    assert rf.hc_params(XING) == 344_064 + 24 + 3
    assert rf.expert_layer_params(XING) == 744_989_046
    assert rf.dense_layer_params(XING) == 128_196_918
    assert rf.weight_params(XING) == 4_792_669_828
    assert rf.weight_bytes(XING) / 1e9 == pytest.approx(9.59, abs=0.01)
    assert rf.latent_dim(XING) == 576
    assert rf.latent_bytes_per_token(XING) == 6_912
    assert rf.hc_bytes_per_token(XING) == 100_352 == (3 * 4 + 2) * 3584 * 2
    assert rf.sublayers(XING) == 12
    pool = XING["engine"]["num_blocks"] * 16 * rf.latent_bytes_per_token(XING)
    assert pool / 1e9 == pytest.approx(1.81, abs=0.01)
    assert rf.weight_bytes(XING) / 16e9 > 0.25          # over the size floor
    assert (rf.weight_bytes(XING) + pool) / 16e9 == pytest.approx(0.71, abs=0.01)


def test_work_of_a_prefill_launch():
    assert rf.hc_bytes(XING, 1000) == 1000 * 12 * 100_352
    # one prompt of 2,048 tokens: W_UKV over every token, half the square
    want = 6 * (2 * 512 * 32 * 256 * 2048 + 2 * 32 * 320 * 2048 ** 2 / 2)
    assert rf.prefill_attention_flops(XING, 2048, 2048 ** 2) == want
    assert rf.experts_flops(XING, 4 * 2048 * 5) == 6 * 3584 * 1024 * 4 * 2048 * 5
    assert rf.experts_read_bytes(XING, 320) == 320 * 22_020_096
    peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # the larger of the two times is what is needed: 40,960 pairs (2,048
    # tokens) take 4.58 ms at the peak, the 320 experts 8.6 ms to read
    need = rf.roofline_seconds(rf.experts_read_bytes(XING, 320),
                               rf.experts_flops(XING, 40_960), peaks)
    assert need == pytest.approx(max(320 * 22_020_096 / 819e9,
                                     40_960 * 22_020_096 / 197e12))
    assert rf.roofline_seconds(
        rf.experts_read_bytes(XING, 320),
        rf.experts_flops(XING, 4 * 4096 * 5), peaks) == pytest.approx(
            4 * 4096 * 5 * 22_020_096 / 197e12)       # 4,096 tokens: compute


# --- the traffic mix ---------------------------------------------------------------------

def test_doc_prefill_backlog_is_stratified():
    items = backlog.sequence(MIX, 3_000_000_019)
    assert len(items) == 1600 and MIX["in_flight"] == 16
    assert not MIX["prime_first_wave"]
    assert all(i["section"] == "window" for i in items)
    assert all(1024 <= i["prompt_len"] <= 4096 and i["max_tokens"] == 8
               and i["greedy"] for i in items)
    # every cycle of 40 is the same stratified multiset in its own order
    first, second = (sorted(i["prompt_len"] for i in items[a:a + 40])
                     for a in (0, 40))
    assert first == second
    assert first[0] == 1024 and first[-1] == 4096
    assert sorted(first)[20] == pytest.approx(2048, rel=0.03)
    assert [i["prompt_len"] for i in items[:40]] != \
        [i["prompt_len"] for i in items[40:80]]
    # the lengths are batch-prefill's: the two prefill cells differ by model
    other = harness.load_json(harness.HERE, "traffic", "batch-prefill.json")
    assert MIX["prompt_len"] == other["prompt_len"] \
        and MIX["output_len"] == other["output_len"] \
        and (MIX["cycle"], MIX["layout_seed"]) == (40, 23)
    lim = harness.traffic_limits(MIX)
    assert (lim["min_prompt"], lim["max_prompt"], lim["max_total"]) == \
        (1024, 4096, 4104)
    again = backlog.sequence(MIX, 7)
    assert [i["prompt_len"] for i in again] == [i["prompt_len"] for i in items]
    assert [i["ids_seed"] for i in again] != [i["ids_seed"] for i in items]


# --- the readers on one synthetic trace -----------------------------------------------------

DEC, PRE = "jit__decode_fn(3)", "jit__prefill_fn(4)"
PEAKS = {"bytes_per_s": 819e9, "flops_per_s": 197e12}


def test_the_innermost_scope_of_a_path():
    assert spans.scope_of("jit(_prefill_fn)/jit(main)/mhc/mhc_sinkhorn/div") \
        == "mhc_sinkhorn"
    assert spans.scope_of("jit(_prefill_fn)/jit(main)/mhc/concatenate") == "mhc"
    assert spans.scope_of("jit(_prefill_fn)/attn/mla_prefill_core/dot") \
        == "mla_prefill_core"
    assert spans.scope_of("jit(_decode_fn)/attn/mla_q/dot") == spans.NONE
    assert spans.scope_of("jit(_prefill_fn)/mlp/moe_router/top_k") == "moe_router"


def fetch(decode, assignments, touched, clamped, entries, ppb=1200):
    return ("engine.fetch", 0.0, 0.1,
            {"bytes": 1, "moe_assignments": assignments, "moe_decode": decode,
             "moe_experts_touched": touched, "moe_max_load": 9,
             "hc_res_clamped": clamped, "hc_entries": entries,
             "hc_sinkhorn_residual_ppb": ppb})


def synthetic():
    planes = {"/device:TPU:0": {
        "modules": [(PRE, 0.0, 1.0), (PRE, 2.0, 1.0), (DEC, 4.0, 0.5)],
        "ops": [("mix.1", 0.0, 0.004), ("sink.2", 0.01, 0.002),
                ("while.9", 0.1, 0.03), ("core.3", 0.1, 0.01),
                ("core.3", 0.11, 0.02),
                ("ragged.4", 0.2, 0.02), ("sort.5", 0.3, 0.001),
                ("mix.1", 2.0, 0.004), ("sink.2", 2.01, 0.002),
                ("core.3", 2.1, 0.03), ("ragged.4", 2.2, 0.02),
                ("mix.1", 4.0, 0.001), ("ragged.4", 4.1, 0.01)]}}
    scopes = {"/device:TPU:0": {
        "mix.1": "mhc_post", "sink.2": "mhc_sinkhorn", "while.9":
        "mla_prefill_core", "core.3": "mla_prefill_core",
        "ragged.4": "moe_experts", "sort.5": "moe_dispatch"}}
    phases = [("engine.dispatch", 0, 0, {}),
              fetch(0, 4 * 2048 * 5, 320, 3, 2048 * 192, 900),
              fetch(0, 4 * 4096 * 5, 320, 1, 4096 * 192, 2500),
              fetch(1, 4 * 16 * 5, 200, 0, 16 * 192)]
    return spans.analyse(planes, phases, scopes)


def test_the_six_metrics_from_one_synthetic_trace():
    a = synthetic()
    assert a["ints"] == {"fetches": 3, "clamped": 4, "entries": 6160 * 192,
                         "residual_ppb": 2500, "prefill_fetches": 2,
                         "assignments": 20 * 6144, "touched": 640}
    assert a["module_launches"] == {"jit__prefill_fn": 2.0,
                                    "jit__decode_fn": 1.0}
    # the while's 0.03 s is counted through its body alone
    assert a["scope_s"]["jit__prefill_fn"]["mla_prefill_core"] == \
        pytest.approx(0.06)
    c = {"model": XING, "engine": XING["engine"], "peaks": PEAKS,
         "traced": {"probe": {"prefill_launches": 2, "prefill_tokens": 5000,
                              "prefill_tokens_sq": 1900 ** 2 + 3100 ** 2}}}
    trace = {"busy_s": 0.2}
    # 0.013 s under mhc in all programs of 0.2 s busy
    assert spans.mhc_share(trace, a) == pytest.approx(100 * 0.013 / 0.2)
    # 5,000 tokens x 12 x 100,352 B at 819 GB/s = 7.35 ms over 12 ms
    assert spans.mhc_roofline(c, a) == pytest.approx(
        100 * (5000 * 12 * 100_352 / 819e9) / 0.012)
    assert spans.mla_prefill_roofline(c, a) == pytest.approx(
        100 * rf.prefill_attention_flops(XING, 5000, 1900 ** 2 + 3100 ** 2)
        / 197e12 / 0.06)
    # 122,880 pairs x 66 MFLOP at 197 TFLOP/s = 13.7 ms; the 640 experts
    # touched take 17.2 ms to read: the larger, over 40 ms
    assert spans.moe_prefill_experts_roofline(c, a) == pytest.approx(
        100 * (640 * 22_020_096 / 819e9) / 0.04)
    assert 20 * 6144 * 6 * 3584 * 1024 / 197e12 < 640 * 22_020_096 / 819e9
    assert spans.moe_prefill_overhead_share(trace, a) == \
        pytest.approx(100 * 0.001 / 0.2)
    assert spans.hc_clamped_share(a) == pytest.approx(1000 * 4 / (6160 * 192))
    # half the prefill launches seen by the probe: the trace's count scales
    c["traced"]["probe"]["prefill_launches"] = 4
    assert spans.mhc_roofline(c, a) == pytest.approx(
        100 * (2500 * 12 * 100_352 / 819e9) / 0.012)
    # and through the files the harness loads, trace or no trace
    for name in OWN:
        mod = harness.load_reader(name)
        assert mod.read(c, None) is None
        check_reader_entry(name, mod)


def check_reader_entry(name, mod):
    m = [e for e in BENCH["per_layer"] if e["name"] == name][0]
    assert (mod.UNIT, mod.LAYER, mod.SOURCE) == \
        (m["unit"], m["layer"], m["source"])


def test_a_trace_without_the_scopes_reads_as_nothing():
    planes = {"/device:TPU:0": {"modules": [(DEC, 0.0, 1.0)],
                                "ops": [("fusion.1", 0.0, 0.5),
                                        ("ragged.4", 0.5, 0.1)]}}
    scopes = {"/device:TPU:0": {"fusion.1": spans.NONE,
                                "ragged.4": "moe_experts"}}
    glm_fetch = ("engine.fetch", 0.0, 0.1, {
        "bytes": 1, "moe_assignments": 8, "moe_decode": 1,
        "moe_experts_touched": 4, "moe_max_load": 3})
    # another model of the family: routed experts, no streams
    assert spans.analyse(planes, [glm_fetch], scopes) is None
    assert spans.analyse({}, [], {}) is None
    c = {"model": XING, "engine": XING["engine"], "peaks": PEAKS,
         "traced": {"probe": {"prefill_launches": 0}}}
    assert spans.mhc_share({"busy_s": 1.0}, None) is None
    assert spans.mhc_roofline(c, None) is None
    assert spans.mla_prefill_roofline(c, None) is None
    assert spans.moe_prefill_experts_roofline(c, None) is None
    assert spans.moe_prefill_overhead_share({"busy_s": 1.0}, None) is None
    assert spans.hc_clamped_share(None) is None
    assert spans.analysis(None) is None
    # a traced run in which no prefill program ran reads no roofline
    a = synthetic()
    assert spans.mhc_roofline(c, a) is None
    # nor does another configuration's file
    glm = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")
    assert spans.mhc_roofline(dict(c, model=glm), a) is None


@pytest.mark.skipif(not os.path.exists(os.path.join(
    harness.HERE, "data", "small_trace.xplane.pb")), reason="no recorded trace")
def test_a_recorded_trace_of_a_dense_model_reads_as_nothing():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    assert spans.load(path) is None


# --- builder and reference at a tiny size --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmarks.models import hc_moe_mla

    return hc_moe_mla.build(TINY, 3_000_000_019)


def test_builder_serves_bf16_and_a_seed_over_31_bits_builds(tiny_model):
    import numpy as np

    from benchmarks.models import hc_moe_mla

    named = dict(tiny_model.named_parameters())
    assert str(named["lm_head.weight"].dtype).endswith("bfloat16")
    assert str(named["llama.layers.0.attn_hc.phi"].dtype).endswith("bfloat16")
    assert named["llama.layers.0.attn_hc.phi"].shape == [256, 24]
    for name in ("llama.layers.1.mlp.e_score_correction_bias",
                 "llama.layers.1.mlp_hc.offsets",
                 "llama.layers.0.attn_hc.gains"):
        assert str(named[name].dtype).endswith("float32"), name
    gains = np.asarray(named["llama.layers.0.attn_hc.gains"]._value)
    assert gains.shape == (3,) and (np.abs(gains - 0.4) < 0.3).all()
    offs = np.asarray(named["llama.layers.1.mlp_hc.offsets"]._value)
    diag = offs[8:].reshape(4, 4).diagonal()
    assert diag.mean() > offs[:8].mean() + 1.0          # B_res = 2 I + noise
    again = hc_moe_mla.build(TINY, 3_000_000_019)
    other = hc_moe_mla.build(TINY, 5)
    pick = lambda m: np.asarray(
        dict(m.named_parameters())["llama.layers.1.mlp_hc.phi"]._value,
        np.float32)
    assert (pick(again) == pick(tiny_model)).all()
    assert (pick(other) != pick(tiny_model)).any()
    w = hc_moe_mla.reference_weights(tiny_model)
    assert "router" not in w["layers"][0] and "gate" in w["layers"][0]
    assert w["layers"][1]["experts_gate_up"].shape == (8, 64, 96)
    assert set(w["layers"][0]["attn_hc"]) == {"phi", "offsets", "gains"}
    with pytest.raises(ValueError, match="n_group"):
        hc_moe_mla.build(dict(TINY, n_group=2), 1)
    # the accepted builder of the single-stream family keeps its refusal
    from benchmarks.models import glm_moe_mla

    with pytest.raises(ValueError, match="rope_scaling"):
        glm_moe_mla.build(dict(TINY, rope_scaling={"type": "yarn"}), 1)


def test_reference_agrees_with_the_model_in_float32_and_is_independent():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from benchmarks.models import hc_moe_mla
    from benchmarks.reference import hc_moe_mla_decoder as ref

    model = hc_moe_mla.build(TINY, 11, dtype="float32")
    ids = np.random.default_rng(0).integers(1, 256, 24).tolist()
    with paddle.no_grad():
        got = model(Tensor(jnp.asarray([ids])))._value[0]
    model.pop_expert_load()
    model.pop_hc_health()
    w = hc_moe_mla.reference_weights(model)
    want = ref.reference_logits(w, TINY, ids)
    assert isinstance(want, np.ndarray) and want.shape == (24, 256)
    res = ref.compare(got, want, 1e-4, 1e-4, margin_eps=1e-6,
                      max_left_out_share=0.0)
    assert res["ok"] and res["rows_compared"] == 24, res
    # the blocks of queries and of vocabulary columns give the same
    ref.QUERY_BLOCK, ref.VOCAB_BLOCK, was = 8, 100, (ref.QUERY_BLOCK,
                                                      ref.VOCAB_BLOCK)
    try:
        np.testing.assert_allclose(ref.reference_logits(w, TINY, ids), want,
                                   rtol=1e-5, atol=1e-6)
    finally:
        ref.QUERY_BLOCK, ref.VOCAB_BLOCK = was
    ref._SEEN[:] = []
    # tight enough to tell a wrong model: one hyper-connection without its
    # dynamic term
    hc0 = dict(w["layers"][0]["attn_hc"])
    no_gain = dict(hc0, gains=hc0["gains"] * 0)
    wrong = dict(w, layers=[dict(w["layers"][0], attn_hc=no_gain)]
                 + w["layers"][1:])
    bad = ref.compare(got, ref.reference_logits(wrong, TINY, ids), 1e-4,
                      1e-4, margin_eps=1e-6, max_left_out_share=0.0)
    assert not bad["ok"]
    src = open(ref.__file__).read()
    assert "paddle_tpu" not in src.replace("``paddle_tpu", "")


# --- a tiny cell end to end on the CPU ---------------------------------------------------------

def test_a_tiny_cell_runs_through_the_launcher(tmp_path):
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-hc.json"), "w") as f:
        json.dump(TINY, f)
    # two prefill buckets, two row buckets, one table width: six programs
    mix = dict(MIX, in_flight=2, lead_in_s=1, trace_s=0.5, cycle=8,
               requests=400, output_len={"dist": "constant", "value": 4},
               prompt_len=dict(MIX["prompt_len"], median=10, min=5, max=12))
    with open(os.path.join(bdir, "traffic", "tiny-prefill.json"), "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    name = "tiny-hc.tiny-prefill"
    bench["configs"].append({"name": "tiny-hc", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny-hc.json", "why": "t"})
    bench["workloads"].append({"name": name, "config": "tiny-hc", "chips": 1,
                               "traffic": "tiny-prefill", "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = io.StringIO()
    assert run.run_cell(name, 3_000_000_019, 1.5, True, root=root,
                        platform="cpu", out=out) == 0
    layer = json.loads(out.getvalue().strip().splitlines()[-1])
    assert layer["correct"] and layer["failed"] == 0 and layer["attempted"] > 4
    assert layer["device"]["platform"] == "cpu"
    chk = layer["detail"]["check"]
    assert chk["ok"] and chk["rows"] == 6 and chk["rows_compared"] >= 1
    assert len(chk["row_margin"]) == 6
    m = layer["metrics"]
    assert m["programs.compiles_in_window.batch"]["value"] == 0
    assert m["cache.preemptions"]["value"] == 0
    assert 0 < m["cache.pool_peak_share"]["value"] <= 100
    assert 0 <= m["scheduler.padding_share"]["value"] < 100
    # no device trace on the CPU: the trace readers leave their metrics out
    assert not set(OWN) & set(m)
