"""The window-and-global-attention / held-experts configuration's benchmark
files: the configuration against the catalog's keys, the cell's entries,
the counts of parameters and bytes against hand arithmetic, the traffic
mix, the seven readers on synthetic traces (the containing-event case among
them), builder and reference at a tiny size, and a tiny cell end to end
through the launcher on the CPU.  No TPU library."""

import io
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import harness, roofline_window_moe as rf
from benchmarks import window_moe_spans as spans
from benchmarks.traffic_kinds import backlog
from test_bm_ssm import GAPS     # the ten ``.gap_`` entries of the batch cells

CFG = harness.load_json(harness.HERE, "configs",
                        "command-a-plus-05-2026.json")
MIX = harness.load_json(harness.HERE, "traffic", "doc-reasoning-decode.json")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELL = "command-a-plus-05-2026.doc-reasoning-decode"
NEW = ("kernels.window_decode_roofline", "kernels.global_decode_roofline",
       "kernels.moe_held_experts_roofline", "programs.window_attn_share",
       "programs.moe_absent_pairs_share", "cache.window_ring_peak_share",
       "engine.moe_held_pair_share")
# what the benchmark held when this cell was accepted (PR 36), in order
CONFIGS_BEFORE = ("mistral-7b-v0.3", "deepseek-llm-7b", "glm-4.7-flash",
                  "ai21-jamba2-3b")
CELLS_BEFORE = ("mistral-7b-v0.3.chat-steady", "deepseek-llm-7b.batch-decode",
                "mistral-7b-v0.3.batch-prefill",
                "glm-4.7-flash.reasoning-decode",
                "ai21-jamba2-3b.reasoning-decode-256")
PEAKS = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {"source": "test", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 48, "num_hidden_layers": 4,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
        "max_position_embeddings": 256, "layer_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": True, "logit_scale": 1,
        "sliding_window": 8, "layer_types": KINDS * 2, "num_experts": 3,
        "experts_held": [2, 3, 5], "n_routed_experts": 8,
        "num_experts_per_tok": 2, "num_shared_experts": 2,
        "norm_topk_prob": True, "builder": "window_moe",
        "reference": "window_moe_decoder",
        "engine": {"num_blocks": 160, "block_size": 16,
                   "pool_dtype": "bfloat16", "max_num_seqs": 8,
                   "max_queue": 64, "prefix_cache": False},
        "check": {"prompt_lens": [40, 5], "decode_steps": 6, "atol": 0.05,
                  "rms_rel": 0.08, "margin_eps": 0.004,
                  "max_left_out_share": 0.5}}


# --- the configuration file and the benchmark's entries ------------------------------

def test_every_published_key_is_unchanged_but_the_four_that_are_cut():
    catalog = dict(
        attention_bias=False, expert_selection_fn="sigmoid",
        first_k_dense_replace=0, head_dim=128, hidden_act="silu",
        hidden_size=4096, intermediate_size=4096, layer_norm_eps=1e-05,
        layer_switch=4, layer_types=KINDS * 8, logit_scale=1,
        max_position_embeddings=200000, model_type="cohere2_moe",
        norm_topk_prob=True, num_attention_heads=128, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=32, num_key_value_heads=8,
        num_shared_experts=4, order_of_interleaved_layers="local_attn_first",
        position_embedding_type="rope_gptj",
        prefix_dense_intermediate_size=16384,
        prefix_dense_sliding_window_pattern=1, rms_norm_eps=None,
        rope_parameters={"rope_theta": 50000, "rope_type": "default"},
        rope_theta=50000, rotary_pct=1,
        shared_expert_combination_strategy="average", sliding_window=4096,
        tf_legacy_loss=False, tie_word_embeddings=True,
        use_embedding_sharing=True, use_gated_activation=True,
        use_parallel_block=True, use_parallel_embedding=False,
        use_qk_norm=False, vocab_size=262144)
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768,
           "max_position_embeddings": 8192}
    assert {k: CFG[k] for k in catalog if k not in cut} == \
        {k: v for k, v in catalog.items() if k not in cut}
    assert {k: CFG[k] for k in cut} == cut
    assert set(CFG["reduced"]) == set(cut)
    assert CFG["published"] == {k: catalog[k] for k in cut}
    assert CFG["experts_held"] == list(range(16))
    assert CFG["n_routed_experts"] == 128       # the router's width
    check_config_entry(BENCH)
    assert "eight" in CFG["deployment"] and "chip 0" in CFG["deployment"]
    assert {"expert_width", "shared_experts", "selection_bias",
            "prefix_dense", "window", "rope_pairing", "norm", "vision_tower",
            "seeded_weights"} <= set(CFG["assumed"])
    eng = CFG["engine"]
    assert (eng["num_blocks"], eng["block_size"], eng["max_num_seqs"],
            eng["max_queue"], eng["prefix_cache"], eng["pool_dtype"]) == \
        (16896, 16, 32, 64, False, "bfloat16")
    chk = CFG["check"]
    assert chk["prompt_lens"] == [4400, 300] and chk["decode_steps"] == 16
    assert (CFG["builder"], CFG["reference"]) == \
        ("window_moe", "window_moe_decoder")


def check_config_entry(bench):
    """The configuration's entry in ``bench``: what it says, and that the
    configurations accepted before it, and no other, stand before it."""
    entry = [c for c in bench["configs"]
             if c["name"] == "command-a-plus-05-2026"][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "max_position_embeddings"]
    assert entry["source"] == CFG["source"] \
        == ("https://huggingface.co/CohereLabs/command-a-plus-05-2026/"
            "blob/main/config.json")
    names = [c["name"] for c in bench["configs"]]
    assert names[:names.index(entry["name"])] == list(CONFIGS_BEFORE)
    assert len(entry["why"]) <= 200


def check_cell_entries(bench):
    """The cell's entries in ``bench``.  Order is held against what was
    accepted BEFORE this cell, never against the end: a later PR appends
    (the contract at the head of ``test_bm_harness.py``)."""
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) <= listed
    assert {"scheduler.rows_per_step.batch", "scheduler.padding_share",
            "cache.pool_peak_share", "cache.preemptions",
            "engine.host_ms_per_step.batch",
            "programs.compiles_in_window.batch", "kernels.sampler_share.batch",
            "device.idle_share.batch", "device.peak_hbm_gb",
            "engine.fetch_mb_per_step.batch", "programs.attn_share.batch",
            "programs.mlp_share.batch", "programs.lm_head_share.batch",
            "kernels.sampler_scope_share.batch", "programs.warm_s_per_program",
            "engine.moe_load_max_over_mean"} <= listed
    # an accepted reader that WOULD read this cell unedited, but whose own
    # accepted test (test_bm_ssm.py) holds its list to one cell
    assert "cache.state_slots_peak_share" not in listed
    assert GAPS <= listed
    assert not {"kernels.paged_decode_roofline", "programs.prefill_flops_share",
                "kernels.mla_decode_roofline", "kernels.moe_experts_roofline",
                "programs.moe_overhead_share", "kernels.ssm_decode_roofline",
                "programs.ssm_share"} & listed
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:cells.index(CELL)] == list(CELLS_BEFORE)
    assert len(bench["workloads"][cells.index(CELL)]["why"]) <= 200
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", ()):      # appended, nothing reordered
            before = m["workloads"][:m["workloads"].index(CELL)]
            assert before == [c for c in CELLS_BEFORE if c in before], \
                m["name"]
    cell = harness.Cell(CELL, bench=bench)
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic == MIX and cell.config == CFG
    for m in cell.per_layer:                # every entry has a reader
        assert callable(cell.reader(m["name"]).read), m["name"]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])             # the seven stand together
    assert names[first:first + len(NEW)] == list(NEW)
    for name in NEW:                        # the new ones only here
        entry = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        reader = harness.load_reader(name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE) == \
            (entry["unit"], entry["layer"], entry["source"])
        if "roofline" in name:
            assert entry["unit"] == "%" and name.endswith("_roofline")


def test_the_cell_lists_the_shared_readers_and_not_the_other_models_counts():
    check_cell_entries(BENCH)


# --- parameters and bytes against the arithmetic of ISSUE 36 -------------------------

def test_counts_at_the_served_sizes():
    assert (rf.window_layers(CFG), rf.global_layers(CFG)) == (3, 1)
    assert rf.attention_params(CFG) == 142_606_336 == \
        2 * 4096 * 16384 + 2 * 4096 * 1024
    assert rf.expert_params(CFG) == 50_331_648 == 3 * 4096 * 4096
    assert rf.expert_bytes(CFG) == 100_663_296
    assert rf.router_params(CFG) == 524_288 == 4096 * 128
    assert rf.layer_params(CFG) == 1_149_767_680 == (
        142_606_336 + 4096 + 524_288 + 201_326_592 + 16 * 50_331_648)
    assert rf.total_params(CFG) == 4_733_292_544 == (
        4 * 1_149_767_680 + 32768 * 4096 + 4096)
    assert rf.weight_bytes(CFG) / 1e9 == pytest.approx(9.47, abs=0.005)
    assert rf.kv_row_bytes(CFG) == 4096
    assert rf.ring_bytes_per_sequence_layer(CFG) == 16_777_216
    assert rf.ring_bytes_per_sequence(CFG) == 50_331_648
    assert rf.page_bytes_per_token(CFG) == 4096
    eng = CFG["engine"]
    rings = (eng["max_num_seqs"] + 1) * rf.ring_bytes_per_sequence(CFG)
    pages = eng["num_blocks"] * 16 * rf.page_bytes_per_token(CFG)
    assert rings == 33 * 50_331_648 and pages == 16_896 * 16 * 4096
    assert rings / 1e9 == pytest.approx(1.66, abs=0.005)
    assert pages / 1e9 == pytest.approx(1.11, abs=0.005)
    assert eng["num_blocks"] == 33 * 512      # every row can reach 8,192 tokens
    held = rf.weight_bytes(CFG) + rings + pages
    assert held / 1e9 == pytest.approx(12.2, abs=0.05) and held / 16e9 > 0.76
    # what every layer would hold as pages at the cell's longest sequence,
    # and at the published 200,000 positions, against ring + pages
    assert 8192 * 4 * 4096 == 134_217_728
    assert rf.ring_bytes_per_sequence(CFG) + 8192 * 4096 == 83_886_080
    assert 200_000 * 4 * 4096 / 1e9 == pytest.approx(3.28, abs=0.01)
    assert (rf.ring_bytes_per_sequence(CFG) + 200_000 * 4096) / 1e9 == \
        pytest.approx(0.87, abs=0.005)


def test_work_of_a_decode_step():
    # 32 rows at ~4,600 tokens: 3 window layers read min(len, 4096) entries
    # a row, the global layer every token, 4 layers their held experts
    assert rf.window_decode_bytes(CFG, 32 * 4096) == 3 * 32 * 16_777_216
    assert rf.window_decode_bytes(CFG, 32 * 4096) / 1e9 == \
        pytest.approx(1.61, abs=0.005)
    assert rf.window_decode_bytes(CFG, 1) == 3 * 4096
    assert rf.global_decode_bytes(CFG, 32 * 4600) == 32 * 4600 * 4096
    assert rf.held_experts_bytes(CFG, 4 * 16) / 1e9 == \
        pytest.approx(6.44, abs=0.005)
    assert rf.held_experts_flops(CFG, 32) == 2 * 50_331_648 * 32
    # bound by the bytes: two tokens an expert a step
    assert rf.roofline_seconds(rf.held_experts_bytes(CFG, 64),
                               rf.held_experts_flops(CFG, 128), PEAKS) == \
        pytest.approx(6.44e9 / 819e9, rel=1e-3)


# --- the traffic mix -------------------------------------------------------------------

def test_doc_reasoning_decode_backlog():
    items = backlog.sequence(MIX, 3_000_000_019)
    assert len(items) == 192 and MIX["in_flight"] == 32
    assert (MIX["kind"], MIX["cycle"], MIX["layout_seed"], MIX["trace_s"],
            MIX["stream"], MIX["prime_first_wave"]) == \
        ("backlog", 32, 23, 3.0, True, True)
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.25, "min": 1024, "max": 2048}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 4608,
                                 "sigma": 0.2, "min": 3072, "max": 6144}
    first, rest = items[:32], items[32:]
    assert all(i["section"] == "lead_in" for i in first)
    assert all(1024 <= i["prompt_len"] <= 2048
               and 3072 <= i["max_tokens"] <= 6144 for i in rest)
    assert all(i["prompt_len"] + i["max_tokens"] <= 8192
               and i["max_tokens"] >= 1 for i in items)
    assert max(i["prompt_len"] for i in first) > 4096   # wraps a ring at once
    assert all(i["greedy"] for i in items)
    lim = harness.traffic_limits(MIX)
    assert (lim["min_prompt"], lim["max_prompt"], lim["max_total"],
            lim["in_flight"]) == (1024, 8191, 8192, 32)
    # the pool holds 32 rows of the longest sequence: nothing is preempted
    assert (CFG["engine"]["num_blocks"] - 1) * 16 >= 32 * lim["max_total"]


# --- the readers on synthetic traces -----------------------------------------------------

DEC, PRE = "jit__decode_fn(3)", "jit__prefill_fn(4)"


def build(rows, tokens):
    return ("engine.build", 0.0, 0.1,
            {"rows": rows, "state_rows": rows, "state_slots_held": 32,
             "window_tokens": tokens})


def fetch(pairs, held, touched, decode=1):
    return ("engine.fetch", 0.0, 0.1,
            {"bytes": 128, "moe_assignments": pairs, "moe_experts_touched": 300,
             "moe_max_load": 20, "moe_decode": decode, "moe_pairs_held": held,
             "moe_held_touched": touched})


def test_sub_scope_anywhere_on_the_path_and_the_outer_scopes():
    assert spans.sub_scope_of(
        "jit(_decode_fn)/jit(main)/attn/attn_window/window_ring_attention/"
        "paged_decode_attention") == "attn_window"
    assert spans.sub_scope_of("jit(_prefill_fn)/attn/attn_global/while/body/"
                              "dot_general") == "attn_global"
    assert spans.sub_scope_of("jit(_decode_fn)/mlp/moe_held_experts/"
                              "moe_dispatch/sort") == "moe_dispatch"
    assert spans.sub_scope_of("jit(_decode_fn)/attn/dot_general") == "attn"
    assert spans.sub_scope_of("jit(_decode_fn)/mlp/add") == "mlp"
    assert spans.sub_scope_of("jit(_decode_fn)/lm_head/dot") == spans.NONE


def test_a_prompts_query_blocks_are_a_while_counted_through_its_body_alone():
    rows = {"modules": [(PRE, 0.0, 3.0), (DEC, 4.0, 1.0)],
            "ops": [("fusion.1", 0.0, 0.2),                      # projections
                    ("while.7", 1.0, 1.0), ("fusion.2", 1.0, 0.5),
                    ("fusion.3", 1.5, 0.5),                      # query blocks
                    ("scatter.4", 2.0, 0.1), ("fusion.9", 2.5, 0.25),
                    ("custom-call.5", 4.0, 0.3), ("fusion.6", 4.3, 0.2)]}
    scopes = {"fusion.1": "attn", "while.7": "attn_window",
              "fusion.2": "attn_window", "fusion.3": "attn_window",
              "scatter.4": "attn_window", "fusion.9": spans.NONE,
              "custom-call.5": "attn_window", "fusion.6": "attn_global"}
    a = spans.analyse({"/device:TPU:0": rows}, [], {"/device:TPU:0": scopes})
    assert a["scope_s"]["jit__prefill_fn"] == pytest.approx(
        {"attn": 0.2, "attn_window": 1.1, spans.NONE: 0.25})
    assert a["scope_s"]["jit__decode_fn"] == pytest.approx(
        {"attn_window": 0.3, "attn_global": 0.2})
    assert a["ints"] is None
    assert spans.scope_s(a, "attn_window") == pytest.approx(1.4)
    assert spans.window_attn_share({"busy_s": 2.8}, a) == pytest.approx(50.0)


def test_the_seven_metrics_from_one_synthetic_trace():
    planes = {"/device:TPU:0": {
        "modules": [(DEC, 0.0, 1.0), (DEC, 2.0, 1.0), (PRE, 4.0, 1.0)],
        "ops": [("ring.1", 0.0, 0.004), ("paged.2", 0.1, 0.006),
                ("sort.3", 0.2, 0.001), ("ragged-dot.4", 0.3, 0.012),
                ("sum.5", 0.4, 0.001), ("shared.6", 0.5, 0.002),
                ("ring.1", 2.0, 0.004), ("paged.2", 2.1, 0.006),
                ("sort.3", 2.2, 0.001), ("ragged-dot.4", 2.3, 0.012),
                ("sum.5", 2.4, 0.001), ("shared.6", 2.5, 0.002),
                ("while.7", 4.0, 0.060), ("body.8", 4.0, 0.060),
                ("dot.9", 4.5, 0.1)]}}
    scopes = {"/device:TPU:0": {
        "ring.1": "attn_window", "paged.2": "attn_global",
        "sort.3": "moe_dispatch", "ragged-dot.4": "moe_experts",
        "sum.5": "moe_combine", "shared.6": "moe_shared",
        "while.7": "attn_window", "body.8": "attn_window",
        "dot.9": spans.NONE}}
    # ONE launch's phases were traced for two decode programs on the device
    phases = [("engine.dispatch", 0, 0, {}), build(32, 100_000),
              fetch(1024, 130, 60), fetch(8192 * 4, 4000, 64, decode=0),
              ("engine.build", 0, 0, {"state_rows": 1, "state_slots_held": 31})]
    a = spans.analyse(planes, phases, scopes)
    assert a["ints"] == {"builds": 1, "window_tokens": 100_000,
                         "window_tokens_max": 100_000, "fetches": 1,
                         "assignments": 1024, "pairs_held": 130,
                         "held_touched": 60}
    c = {"model": CFG, "engine": CFG["engine"], "peaks": PEAKS,
         "traced": {"probe": {"decode_kv_tokens": 2 * 147_200,
                              "decode_rows": 64}}}
    # 2 launches x 100,000 entries x 3 layers x 4,096 B at 819 GB/s over 8 ms
    assert spans.window_decode_roofline(c, a) == pytest.approx(
        100 * (2 * 100_000 * 3 * 4096 / 819e9) / 0.008)
    # 294,400 tokens x 4,096 B over 12 ms
    assert spans.global_decode_roofline(c, a) == pytest.approx(
        100 * (294_400 * 4096 / 819e9) / 0.012)
    # 2 launches x 60 held experts x 100.7 MB over 24 ms
    assert spans.moe_held_experts_roofline(c, a) == pytest.approx(
        100 * (120 * 100_663_296 / 819e9) / 0.024)
    for v in (spans.window_decode_roofline(c, a),
              spans.global_decode_roofline(c, a),
              spans.moe_held_experts_roofline(c, a)):
        assert 0 < v < 100
    assert spans.window_attn_share({"busy_s": 0.5}, a) == pytest.approx(
        100 * (0.008 + 0.060) / 0.5)
    assert spans.moe_absent_pairs_share(a) == pytest.approx(
        100 * 0.004 / 0.032)
    assert spans.window_ring_peak_share(c, a) == pytest.approx(
        100 * 100_000 / (32 * 4096))
    assert spans.moe_held_pair_share(a) == pytest.approx(100 * 130 / 1024)
    for name in NEW:
        assert harness.load_reader(name).read(c, None) is None


def test_a_trace_without_the_scopes_reads_as_nothing():
    planes = {"/device:TPU:0": {"modules": [(DEC, 0.0, 1.0)],
                                "ops": [("fusion.1", 0.0, 0.5)]}}
    none = {"/device:TPU:0": {"fusion.1": spans.NONE}}
    assert spans.analyse(planes, [], none) is None
    assert spans.analyse(planes, [("engine.build", 0, 0, {"rows": 4})], none) \
        is None
    assert spans.analyse({}, [], {}) is None
    c = {"model": CFG, "engine": CFG["engine"], "peaks": {}, "traced": {}}
    for f in (spans.window_decode_roofline, spans.global_decode_roofline,
              spans.moe_held_experts_roofline, spans.window_ring_peak_share):
        assert f(c, None) is None
    assert spans.window_attn_share({"busy_s": 1.0}, None) is None
    assert spans.moe_absent_pairs_share(None) is None
    assert spans.moe_held_pair_share(None) is None
    assert spans.analysis(None) is None
    # the integers alone (a CPU run: no device plane) still read
    a = spans.analyse(planes, [build(3, 20), fetch(48, 7, 5)], none)
    assert spans.window_ring_peak_share(c, a) == pytest.approx(
        100 * 20 / (32 * 4096))
    assert spans.moe_held_pair_share(a) == pytest.approx(100 * 7 / 48)
    assert spans.window_decode_roofline(c, a) is None
    # GLM's expert scopes under another model read nothing of this
    glm = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")
    other = dict(c, model=glm, peaks=PEAKS,
                 traced={"probe": {"decode_kv_tokens": 9}})
    moe = spans.analyse(planes, [build(3, 20)],
                        {"/device:TPU:0": {"fusion.1": "attn_window"}})
    assert spans.window_decode_roofline(other, moe) is None
    assert spans.global_decode_roofline(other, moe) is None


@pytest.mark.skipif(not os.path.exists(os.path.join(
    harness.HERE, "data", "small_trace.xplane.pb")), reason="no recorded trace")
def test_a_recorded_trace_of_a_dense_model_reads_as_nothing():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    assert spans.load(path) is None


# --- builder and reference at a tiny size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    return harness.load_module("models", "window_moe").build(TINY, 5)


def test_builder_serves_bf16_a_share_of_the_experts_and_the_routers_width(
        tiny_model):
    import jax.numpy as jnp

    named = dict(tiny_model.named_parameters())
    assert all(p._value.dtype == jnp.bfloat16 for p in named.values())
    mlp = "llama.layers.0.mlp."
    assert tuple(named[mlp + "gate.weight"].shape) == (64, 8)
    assert tuple(named[mlp + "w_gate_up"].shape) == (3, 64, 96)
    assert tuple(named[mlp + "shared_experts.gate_proj.weight"].shape) == \
        (64, 96)
    assert float(named["llama.norm.weight"]._value.min()) == 1.0
    assert 0.015 < float(named[mlp + "w_down"]._value.astype(
        jnp.float32).std()) < 0.025
    assert tiny_model.config.experts_held == (2, 3, 5)
    assert tiny_model.config.layer_types == tuple(KINDS)    # the first four
    builder = harness.load_module("models", "window_moe")
    with pytest.raises(ValueError, match="use_qk_norm"):
        builder.model_config(dict(TINY, use_qk_norm=True))
    with pytest.raises(ValueError, match="experts_held lists"):
        builder.model_config(dict(TINY, num_experts=2))


def test_a_seed_over_31_bits_builds(tiny_model):
    builder = harness.load_module("models", "window_moe")
    big = builder.build(TINY, 3_000_000_019)
    a = dict(big.named_parameters())["llama.layers.1.self_attn.q_proj.weight"]
    b = dict(tiny_model.named_parameters())[
        "llama.layers.1.self_attn.q_proj.weight"]
    assert not np.array_equal(np.asarray(a._value, np.float32),
                              np.asarray(b._value, np.float32))


def test_reference_agrees_with_the_model_in_float32_and_tells_a_wrong_one():
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    builder = harness.load_module("models", "window_moe")
    ref = harness.load_module("reference", "window_moe_decoder")
    model = builder.build(TINY, 7, dtype="float32")
    ids = np.random.default_rng(1).integers(1, 256, 30).tolist()
    with paddle.no_grad():
        got = np.asarray(model(Tensor(jnp.asarray([ids])))._value[0])
    weights = builder.reference_weights(model)
    want = np.asarray(ref.reference_logits(weights, TINY, ids))
    res = ref.compare(got, want, 1e-4, 1e-4, margin_eps=0.0)
    assert res["ok"] and res["rows"] == 30 and res["left_out_share"] == 0.0
    # the same reference told the whole window is visible: not this model
    wide = np.asarray(ref.reference_logits(
        weights, dict(TINY, sliding_window=64), ids))
    assert not ref.compare(got, wide, 1e-4, 1e-4, margin_eps=0.0)["ok"]
    assert np.abs(wide[:8] - want[:8]).max() < 1e-5     # inside the window
    # and held every expert: what the absent ones add is not left out
    assert not ref.compare(got, np.asarray(ref.reference_logits(
        weights, dict(TINY, experts_held=[2, 3, 4]), ids)), 1e-4, 1e-4,
        margin_eps=0.0)["ok"]


def test_compare_holds_both_limits_and_the_near_tie_rule():
    ref = harness.load_module("reference", "window_moe_decoder")
    want = np.random.default_rng(0).normal(size=(6, 40)).astype(np.float32)
    ok = ref.compare(want + 0.01, want, 0.05, 0.05, margins=np.ones(6),
                     margin_eps=0.01, max_left_out_share=0.5)
    assert ok["ok"] and ok["rows_compared"] == 6
    spike = want.copy()
    spike[2, 3] += 0.5
    assert not ref.compare(spike, want, 0.05, 1.0, margins=np.ones(6),
                           margin_eps=0.01, max_left_out_share=0.5)["ok"]
    # the same row at a routing near-tie is left out, and said so
    tie = ref.compare(spike, want, 0.05, 1.0,
                      margins=np.array([1, 1, 0.001, 1, 1, 1.0]),
                      margin_eps=0.01, max_left_out_share=0.5)
    assert tie["ok"] and tie["rows_compared"] == 5
    assert tie["left_out_share"] == pytest.approx(1 / 6)
    assert not ref.compare(want * 1.2, want, 10.0, 0.05, margins=np.ones(6),
                           margin_eps=0.01, max_left_out_share=0.5)["ok"]


# --- a tiny cell end to end on the CPU -----------------------------------------------------

def test_a_tiny_cell_runs_through_the_launcher(tmp_path):
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-window.json"), "w") as f:
        json.dump(TINY, f)
    mix = dict(MIX, requests=1024, in_flight=8, lead_in_s=1, trace_s=0.5,
               cycle=8,
               prompt_len=dict(MIX["prompt_len"], median=24, min=8, max=48),
               output_len=dict(MIX["output_len"], median=16, min=8, max=32))
    with open(os.path.join(bdir, "traffic", "tiny-docs.json"), "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    name = "tiny-window.tiny-docs"
    bench["configs"].append({"name": "tiny-window", "source": "test",
                             "reduced": [], "why": "t",
                             "file": "benchmarks/configs/tiny-window.json"})
    bench["workloads"].append({"name": name, "config": "tiny-window",
                               "chips": 1, "traffic": "tiny-docs", "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = io.StringIO()
    assert run.run_cell(name, 3_000_000_019, 2.0, True, root=root,
                        platform="cpu", out=out) == 0
    layer = json.loads(out.getvalue().strip().splitlines()[-1])
    assert layer["correct"] and layer["failed"] == 0 and layer["attempted"] > 8
    assert layer["device"]["platform"] == "cpu"
    chk = layer["detail"]["check"]
    assert chk["ok"] and chk["rows"] == 14 and chk["decode_steps"] == 6
    m = layer["metrics"]
    assert m["programs.compiles_in_window.batch"]["value"] == 0
    assert m["cache.preemptions"]["value"] == 0
    assert 0 < m["cache.pool_peak_share"]["value"] <= 100
    assert m["scheduler.rows_per_step.batch"]["value"] > 1
    assert m["programs.warm_s_per_program"]["value"] > 0
    # no device trace on the CPU: the trace readers leave their metrics out
    assert not set(NEW) & set(m)
