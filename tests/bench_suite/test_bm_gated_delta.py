"""The gated delta-rule / gated latent-attention / held-experts
configuration's benchmark files (ISSUE 49): the configuration file against
the catalog's row, the benchmark's entries as ``check_*(bench)`` functions
that take an append (the contract at the head of ``test_bm_harness.py``:
no last place, no counts), the counts of parameters, bytes and operations
against numbers worked by hand, the traffic mix, the six readers on
synthetic traces, builder and reference at a tiny size, a tiny cell end to
end through the launcher on the CPU, and the decode step's state update
compiled at the published widths for the chip that is not attached."""

import io
import json
import os
import shutil

import pytest

from benchmarks import gated_delta_spans as spans, harness
from benchmarks import roofline_gated_delta as rf
from benchmarks.traffic_kinds import backlog

GIGA = harness.load_json(harness.HERE, "configs", "gigachat3.5-432b-a28b.json")
MIX = harness.load_json(harness.HERE, "traffic", "delta-reasoning-decode.json")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = "gigachat3.5-432b-a28b"
CELL = CONFIG + ".delta-reasoning-decode"
NEW = ("kernels.gdn_decode_roofline", "kernels.gdn_chunk_roofline",
       "programs.gdn_share", "kernels.gdn_cell_experts_roofline",
       "kernels.gdn_cell_mla_decode_roofline", "cache.gdn_slots_peak_share")
REDUCED = ("num_hidden_layers", "first_k_dense_replace",
           "full_attention_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings", "num_nextn_predict_layers")
PEAKS = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
TINY = {
    "source": "test", "vocab_size": 96, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 48,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "q_lora_rank": 32, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 3, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "first_k_dense_replace": 1, "full_attention_layers": [3],
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_sigmoid_gate_scale": 2,
    "linear_attn_o_norm_eps": 1e-6, "layernorm_gating_weight": 2,
    "gated_attention": True, "swiglu_limit": 10,
    "rope_scaling": {"type": "yarn", "factor": 8.0, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32},
    "published": {"n_routed_experts": 8}, "experts_held": [1, 4, 6],
    "reduced": {}, "builder": "gated_delta_moe_mla",
    "reference": "gated_delta_moe_mla_decoder",
    "engine": {"num_blocks": 160, "block_size": 16, "pool_dtype": "bfloat16",
               "max_num_seqs": 8, "max_queue": 64, "prefix_cache": False},
    "check": {"prompt_lens": [70, 25], "decode_steps": 6, "atol": 0.05,
              "rms_rel": 0.08, "margin_eps": 0.02, "max_left_out_share": 0.5,
              "max_left_out_a_prompt": 0.5,
              "state_rel": {"0": 0.01, "1": 0.03, "2": 0.3, "4": 0.5}}}

# a forward pass with no engine has no slot whose state could be held
BARE = dict(TINY, check={k: v for k, v in TINY["check"].items()
                         if k != "state_rel"})


# --- the configuration file and the benchmark's entries ------------------------------

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_file_differs_from_the_catalogs_row_in_the_reduced_keys_alone():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "GigaChat3.5-432B-A28B"][0]
    catalog = row["config"]
    assert GIGA["source"] == row["source_url"]
    assert {k for k in catalog if GIGA.get(k) != catalog[k]} == set(REDUCED)
    assert GIGA["published"] == {k: catalog[k] for k in REDUCED}


def test_every_width_is_kept_and_seven_keys_are_reduced():
    assert set(GIGA["reduced"]) == set(REDUCED)
    assert (GIGA["num_hidden_layers"], GIGA["first_k_dense_replace"],
            GIGA["full_attention_layers"], GIGA["n_routed_experts"],
            GIGA["vocab_size"], GIGA["max_position_embeddings"],
            GIGA["num_nextn_predict_layers"]) == (5, 1, [3], 16, 16032, 8192, 0)
    # no width is cut
    assert (GIGA["hidden_size"], GIGA["intermediate_size"],
            GIGA["moe_intermediate_size"], GIGA["num_attention_heads"],
            GIGA["q_lora_rank"], GIGA["kv_lora_rank"],
            GIGA["qk_nope_head_dim"], GIGA["qk_rope_head_dim"],
            GIGA["v_head_dim"], GIGA["num_experts_per_tok"],
            GIGA["linear_num_key_heads"], GIGA["linear_num_value_heads"],
            GIGA["linear_key_head_dim"], GIGA["linear_value_head_dim"],
            GIGA["linear_conv_kernel_dim"]) == \
        (7168, 18432, 2048, 64, 1536, 512, 128, 64, 128, 8, 32, 64, 128, 128, 4)
    assert GIGA["published"]["n_routed_experts"] == 256
    assert GIGA["experts_held"] == list(range(16))
    assert {"norm_form", "swiglu_limit", "attention_gate", "rope_pairing",
            "delta_order", "delta_gates", "delta_state", "router",
            "nextn_is_sparse", "seeded_weights"} <= set(GIGA["assumed"])
    assert "sixteen TPU v5e chips" in GIGA["deployment"] \
        and "4,731,722,752" in GIGA["deployment"] \
        and "4 tokens a decode step" in GIGA["deployment"]
    eng = GIGA["engine"]
    assert (eng["num_blocks"], eng["block_size"], eng["max_num_seqs"],
            eng["prefix_cache"], eng["pool_dtype"]) == \
        (129 * 256, 16, 128, False, "bfloat16")
    chk = GIGA["check"]
    assert chk["prompt_lens"] == [1100, 300] and chk["decode_steps"] == 16
    assert all(n % 64 for n in chk["prompt_lens"])      # both end inside a chunk
    assert {"atol", "rms_rel", "margin_eps", "max_left_out_share",
            "max_left_out_a_prompt", "state_rel", "why"} <= set(chk)
    # the margin rule discriminates (every row's margin is under 0.007), and
    # every delta-rule layer's state is held, the first layer's tightest
    assert chk["margin_eps"] < 0.007 and chk["max_left_out_share"] < 0.5
    assert set(chk["state_rel"]) == {"0", "1", "2", "4"}
    assert chk["state_rel"]["0"] == min(chk["state_rel"].values())
    assert (GIGA["builder"], GIGA["reference"]) == \
        ("gated_delta_moe_mla", "gated_delta_moe_mla_decoder")
    check_config_entry(BENCH)


def check_config_entry(bench):
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == GIGA["source"] == \
        "https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json"
    assert entry["file"] == "benchmarks/configs/gigachat3.5-432b-a28b.json"
    names = [c["name"] for c in bench["configs"]]
    assert names.index(CONFIG) > names.index("evabyte-6.5b")


def check_cell_entries(bench):
    """The cell's entries in ``bench``: membership and order against what
    was accepted before, no count of names a later PR's entries move."""
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("evabyte-6.5b.byte-reasoning-decode")
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
            "programs.warm_s_per_program"} <= listed
    # readers of other models' counts and scopes are not asked to read here
    # (engine.moe_load_max_over_mean divides by the file's n_routed_experts,
    # which is the 16 HELD here and not the router's 256)
    assert not {"kernels.paged_decode_roofline", "kernels.mla_decode_roofline",
                "kernels.moe_experts_roofline", "kernels.ssm_decode_roofline",
                "engine.moe_load_max_over_mean",
                "cache.state_slots_peak_share"} & listed
    cell = harness.Cell(CELL, bench=bench)
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic == MIX and cell.config == GIGA
    for m in cell.per_layer:                # every entry has a reader
        assert callable(cell.reader(m["name"]).read), m["name"]
    names = [m["name"] for m in bench["per_layer"]]
    for name in NEW:                        # the new ones only here
        entry = bench["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        assert names.index(name) > names.index("cache.eva_summary_peak_share")
        reader = harness.load_reader(name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE) == \
            (entry["unit"], entry["layer"], entry["source"])


def test_the_cell_lists_its_readers_and_the_shared_ones():
    check_cell_entries(BENCH)


# --- parameters, bytes and operations against the arithmetic of ISSUE 49 -----------------

def test_counts_at_the_published_widths():
    assert rf.router_width(GIGA) == 256
    assert (rf.delta_layers(GIGA), rf.latent_layers(GIGA)) == (4, 1)
    assert rf.delta_conv_dim(GIGA) == 16384 == 4096 + 4096 + 8192
    assert rf.latent_attention_params(GIGA) == 159_844_352 == (
        7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
        + 8192 * 7168 + 7168 * 8192 + 1536 + 512)
    assert rf.delta_mixer_params(GIGA) == 235_864_320 == (
        7168 * (4096 + 4096 + 8192 + 8192 + 128) + 16384 * 4 + 8192 * 7168
        + 64 + 64 + 128)
    assert rf.expert_params(GIGA) == 44_040_192 == 3 * 7168 * 2048
    assert rf.expert_bytes(GIGA) == 88_080_384
    assert rf.dense_ffn_params(GIGA) == 396_361_728
    assert rf.router_params(GIGA) == 256 * 7168 + 256
    assert [rf.layer_params(GIGA, i) for i in range(5)] == [
        632_254_720, 986_411_520, 986_411_520, 910_391_552, 986_411_520]
    assert rf.total_params(GIGA) == 4_731_722_752
    assert rf.weight_bytes(GIGA) == 9_463_445_504
    # a sequence, a layer: 64 x 128 x 128 float32 and 3 x 16,384 bf16
    assert rf.state_bytes_per_sequence_layer(GIGA) == 4_292_608 == \
        4_194_304 + 98_304
    assert rf.state_bytes_per_sequence(GIGA) == 17_170_432
    eng = GIGA["engine"]
    slots = rf.slot_bytes(GIGA, eng["max_num_seqs"])
    pages = rf.page_bytes(GIGA, eng["num_blocks"], eng["block_size"])
    assert slots == 129 * 4 * 4_292_608 == 2_214_985_728
    assert pages == 33_024 * 16 * 1_280 == 676_331_520
    assert rf.latent_row_bytes(GIGA) == 1_152
    held = rf.weight_bytes(GIGA) + slots + pages
    assert held == 12_354_762_752 and 0.77 < held / 16e9 < 0.78
    # the cut is the guide's floor: a whole period, >= 4 layers behind the
    # dense one, >= 8 experts, >= an eighth of the vocabulary
    assert GIGA["num_hidden_layers"] - GIGA["first_k_dense_replace"] >= 4
    assert GIGA["n_routed_experts"] >= 8
    assert GIGA["vocab_size"] * 8 >= GIGA["published"]["vocab_size"]


def test_work_of_a_decode_step_and_of_a_prompts_chunks():
    # 128 real rows: every row's state of 4 layers read once and written once
    assert rf.decode_state_bytes(GIGA, 128) == 2 * 128 * 17_170_432
    assert rf.decode_state_bytes(GIGA, 128) / 1e9 == pytest.approx(4.40, abs=0.01)
    # the step the cell's why describes: weights but the embedding, the
    # state both ways, the rows' latents (~2,300 tokens a row)
    step = (rf.weight_bytes(GIGA) - 16032 * 7168 * 2
            + rf.decode_state_bytes(GIGA, 128)
            + rf.decode_latent_bytes(GIGA, 128 * 2300))
    assert step / 1e9 == pytest.approx(14.0, abs=0.1)
    assert rf.roofline_seconds(step, 0.0, PEAKS) == pytest.approx(17.1e-3,
                                                                  rel=0.01)
    assert rf.held_experts_bytes(GIGA, 64) / 1e9 == pytest.approx(5.64, abs=0.01)
    assert rf.held_experts_flops(GIGA, 3) == 6 * 44_040_192
    assert rf.decode_latent_bytes(GIGA, 1000) == 1_152_000
    assert rf.decode_latent_flops(GIGA, 1) == 2 * 64 * (512 + 512 + 64)
    # a chunk of 64 of one head: K K^T and Q K^T, the solve with 256
    # right-hand sides, three products with the 128 x 128 state, (QK^T) U
    assert rf.chunk_flops_per_head(GIGA) == 10_485_760 == (
        2 * 2 * 64 * 64 * 128 + 64 * 64 * 256 + 3 * 2 * 64 * 128 * 128
        + 2 * 64 * 64 * 128)
    assert rf.chunk_flops(GIGA, 64) == 10_485_760 * 64 * 4
    assert rf.chunk_flops(GIGA, 1024) == 16 * rf.chunk_flops(GIGA, 64)
    assert rf.roofline_seconds(1e9, 197e12, PEAKS) == pytest.approx(1.0)


# --- the traffic mix -------------------------------------------------------------------

def test_delta_reasoning_decode_is_reasoning_decodes_lengths():
    items = backlog.sequence(MIX, 3_000_000_019)
    assert len(items) == 512 and MIX["in_flight"] == 128
    assert (MIX["kind"], MIX["cycle"], MIX["layout_seed"], MIX["trace_s"],
            MIX["stream"]) == ("backlog", 32, 23, 3.0, True)
    assert "why_departs" in MIX and MIX["lead_in_s"] >= 20
    first, rest = items[:128], items[128:]
    assert all(i["section"] == "lead_in" for i in first)
    assert all(512 <= i["prompt_len"] <= 1024
               and 1024 <= i["max_tokens"] <= 3072 for i in rest)
    assert all(i["greedy"] for i in items)
    lim = harness.traffic_limits(MIX)
    assert (lim["min_prompt"], lim["max_prompt"], lim["max_total"],
            lim["in_flight"]) == (512, 1024 + 3071, 4096, 128)
    eng = GIGA["engine"]
    assert lim["max_total"] <= (eng["num_blocks"] // 129) * 16
    # the latent cell's lengths and concurrency: the two differ by model
    same = harness.load_json(harness.HERE, "traffic", "reasoning-decode.json")
    keys = ("kind", "requests", "in_flight", "prompt_len", "output_len",
            "sampling", "stream", "cycle", "layout_seed", "prime_first_wave",
            "trace_s")
    assert {k: MIX[k] for k in keys} == {k: same[k] for k in keys}


# --- the readers on synthetic traces -----------------------------------------------------

DEC, PRE = "jit__decode_fn(3)", "jit__prefill_fn(4)"
TPU = "/device:TPU:0"


def build(rows, held):
    return ("engine.build", 0.0, 0.1,
            {"rows": rows, "state_rows": rows, "state_slots_held": held})


def fetch(pairs, touched, decode=1):
    return ("engine.fetch", 0.0, 0.1,
            {"moe_assignments": 4096, "moe_pairs_held": pairs,
             "moe_held_touched": touched, "moe_decode": decode})


def test_sub_scope_anywhere_on_the_path_and_the_outer_scope():
    assert spans.sub_scope_of(
        "jit(_decode_fn)/gdn/gated_delta_mixer/gdn_step/mul") == "gdn_step"
    assert spans.sub_scope_of(
        "jit(_prefill_fn)/gdn/gdn_chunk/while/body/dot_general") == "gdn_chunk"
    assert spans.sub_scope_of("jit(_decode_fn)/gdn/zero_centered_gated_norm/mul") \
        == spans.OUTER
    assert spans.sub_scope_of("jit(_decode_fn)/attn/mla_decode_core/dot") \
        == "mla_decode_core"
    assert spans.sub_scope_of("jit(_decode_fn)/attn/mla_gate/mul") == "mla_gate"
    assert spans.sub_scope_of("jit(_decode_fn)/mlp/moe_experts/ragged_dot") \
        == "moe_experts"
    assert spans.sub_scope_of("jit(_decode_fn)/attn/mla_q/dot") == spans.NONE
    assert spans.sub_scope_of("jit(_decode_fn)/mlp/dot") == spans.NONE


def test_the_chunks_carry_is_a_while_and_is_counted_through_its_body_alone():
    rows = {"modules": [(PRE, 0.0, 3.0), (DEC, 4.0, 1.0)],
            "ops": [("fusion.1", 0.0, 0.2),                      # in-projection
                    ("while.7", 1.0, 1.0), ("fusion.2", 1.0, 0.5),
                    ("fusion.3", 1.5, 0.5),                      # the carry's body
                    ("solve.4", 2.0, 0.1), ("fusion.9", 2.5, 0.25),
                    ("gather.5", 4.0, 0.3), ("fusion.6", 4.3, 0.2)]}
    scopes = {"fusion.1": "gdn_in_proj", "while.7": "gdn_chunk",
              "fusion.2": "gdn_chunk", "fusion.3": "gdn_chunk",
              "solve.4": "gdn_chunk", "fusion.9": spans.NONE,
              "gather.5": "gdn_step", "fusion.6": "gdn_step"}
    a = spans.analyse({TPU: rows}, [], {TPU: scopes})
    assert a["scope_s"]["jit__prefill_fn"] == pytest.approx(
        {"gdn_in_proj": 0.2, "gdn_chunk": 1.1, spans.NONE: 0.25})
    assert a["scope_s"]["jit__decode_fn"] == pytest.approx({"gdn_step": 0.5})
    assert a["ints"] is None
    assert spans.scope_s(a, "gdn_chunk", "jit__prefill_fn") == pytest.approx(1.1)
    assert spans.scope_s(a, "gdn_step", "jit__prefill_fn") == 0.0


def test_the_six_metrics_from_one_synthetic_trace():
    planes = {TPU: {
        "modules": [(DEC, 0.0, 1.0), (DEC, 2.0, 1.0), (PRE, 4.0, 1.0)],
        "ops": [("gather.1", 0.0, 0.020), ("fusion.2", 0.1, 0.012),
                ("norm.8", 0.2, 0.001), ("ragged.5", 0.3, 0.010),
                ("walk.6", 0.4, 0.002),
                ("gather.1", 2.0, 0.020), ("fusion.2", 2.1, 0.012),
                ("ragged.5", 2.3, 0.010), ("walk.6", 2.4, 0.002),
                ("while.7", 4.0, 0.060), ("body.3", 4.0, 0.060),
                ("dot.4", 4.5, 0.1)]}}
    scopes = {TPU: {"gather.1": "gdn_step", "fusion.2": "gdn_step",
                    "norm.8": spans.OUTER, "ragged.5": "moe_experts",
                    "walk.6": "mla_decode_core", "while.7": "gdn_chunk",
                    "body.3": "gdn_chunk", "dot.4": spans.NONE}}
    phases = [("engine.dispatch", 0, 0, {}), build(120, 127), build(124, 128),
              ("engine.build", 0, 0, {"state_rows": 1, "state_slots_held": 126}),
              fetch(60, 52), fetch(68, 56), fetch(900, 64, decode=0)]
    a = spans.analyse(planes, phases, scopes)
    assert a["ints"] == {"decode_builds": 2, "state_rows": 244, "fetches": 2,
                         "pairs_held": 128, "held_touched": 108}
    assert a["slots"] == {"launches": 3, "held_max": 128, "rows": 245}
    assert a["module_launches"] == {"jit__decode_fn": 2.0,
                                    "jit__prefill_fn": 1.0}
    c = {"model": GIGA, "engine": GIGA["engine"], "peaks": PEAKS,
         "traced": {"probe": {"decode_rows": 244, "decode_kv_tokens": 500_000,
                              "prefill_launches": 1, "prefill_tokens": 1600}}}
    # 244 rows x 2 x 17,170,432 B at 819 GB/s = 10.2 ms over 64 ms
    assert spans.gdn_decode_roofline(c, a) == pytest.approx(
        100 * (244 * 2 * 17_170_432 / 819e9) / 0.064)
    # 1,600 tokens = 25 chunks x 64 heads x 4 layers x 10,485,760 at 197
    # TFLOP/s = 0.34 ms over 60 ms
    assert spans.gdn_chunk_roofline(c, a) == pytest.approx(
        100 * (25 * 64 * 4 * 10_485_760 / 197e12) / 0.060)
    # 108 (layer, held expert) pairs touched x 88,080,384 B over 20 ms
    assert spans.cell_experts_roofline(c, a) == pytest.approx(
        100 * (108 * 88_080_384 / 819e9) / 0.020)
    # 500,000 latent rows x 1,152 B over 4 ms
    assert spans.cell_mla_decode_roofline(c, a) == pytest.approx(
        100 * (500_000 * 1_152 / 819e9) / 0.004)
    assert spans.gdn_share({"busy_s": 0.5}, a) == pytest.approx(
        100 * (0.064 + 0.001 + 0.060) / 0.5)
    assert spans.slots_peak_share(c, a) == pytest.approx(100.0)
    for v in (spans.gdn_decode_roofline(c, a), spans.gdn_chunk_roofline(c, a),
              spans.cell_experts_roofline(c, a),
              spans.cell_mla_decode_roofline(c, a)):
        assert 0 < v < 100
    # no prefill in the traced slice: the chunked rule's share is left out
    quiet = dict(c, traced={"probe": dict(c["traced"]["probe"],
                                          prefill_tokens=0)})
    assert spans.gdn_chunk_roofline(quiet, a) is None


def test_a_trace_without_the_scopes_reads_as_nothing():
    planes = {TPU: {"modules": [(DEC, 0.0, 1.0)],
                    "ops": [("fusion.1", 0.0, 0.5)]}}
    none = {TPU: {"fusion.1": spans.NONE}}
    assert spans.analyse(planes, [], none) is None
    assert spans.analyse(planes, [build(3, 5)], none) is None
    # another model's experts and latent walk alone are not this cell's
    other = {TPU: {"fusion.1": "moe_experts"}}
    assert spans.analyse(planes, [fetch(3, 2)], other) is None
    assert spans.analyse({}, [], {}) is None
    c = {"model": GIGA, "engine": GIGA["engine"], "peaks": {}, "traced": {}}
    for fn in (spans.gdn_decode_roofline, spans.gdn_chunk_roofline,
               spans.cell_experts_roofline, spans.cell_mla_decode_roofline,
               spans.slots_peak_share):
        assert fn(c, None) is None
    assert spans.gdn_share({"busy_s": 1.0}, None) is None
    assert spans.analysis(None) is None
    for name in NEW:
        assert harness.load_reader(name).read(c, None) is None
    # a model without the mixer under these scopes would read nothing
    glm = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")
    a = spans.analyse(planes, [build(3, 5)], {TPU: {"fusion.1": "gdn_step"}})
    dense = dict(c, model=glm, peaks=PEAKS)
    assert spans.gdn_decode_roofline(dense, a) is None
    assert spans.slots_peak_share(dense, a) is None
    assert spans.slots_peak_share(dict(c, peaks=PEAKS), a) == \
        pytest.approx(100 * 5 / 128)


@pytest.mark.skipif(not os.path.exists(os.path.join(
    harness.HERE, "data", "small_trace.xplane.pb")), reason="no recorded trace")
def test_a_recorded_trace_of_a_dense_model_reads_as_nothing():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    assert spans.load(path) is None


# --- builder and reference at a tiny size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmarks.models import gated_delta_moe_mla

    return gated_delta_moe_mla.build(TINY, 3_000_000_019)


def test_builder_serves_bf16_with_a_float32_recurrence_and_seeded_decays(
        tiny_model):
    import numpy as np

    from benchmarks.models import gated_delta_moe_mla as b

    named = dict(tiny_model.named_parameters())
    dt = lambda n: str(named[n].dtype)      # noqa: E731
    assert dt("llama.embed_tokens.weight").endswith("bfloat16")
    assert dt("lm_head.weight").endswith("bfloat16")            # untied
    assert dt("llama.layers.0.delta.in_proj.weight").endswith("bfloat16")
    assert dt("llama.layers.0.delta.A_log").endswith("float32")
    assert dt("llama.layers.0.delta.dt_bias").endswith("float32")
    assert dt("llama.layers.1.mlp.e_score_correction_bias").endswith("float32")
    assert "llama.layers.3.self_attn.g_proj.weight" in named    # the gate
    assert "llama.layers.3.delta.A_log" not in named
    assert "llama.layers.0.mlp.gate_proj.weight" in named       # dense first
    assert tuple(named["llama.layers.1.mlp.w_gate_up"].shape) == (3, 64, 96)
    assert tuple(named["llama.layers.1.mlp.gate.weight"].shape) == (64, 8)
    a = np.exp(np.asarray(named["llama.layers.2.delta.A_log"]._value))
    assert a.shape == (4,) and (a >= 1).all() and (a <= 16).all()
    w = np.asarray(named["llama.layers.0.input_layernorm.weight"]._value,
                   np.float32)
    assert 0 < np.abs(w).max() < 0.1                            # about 0
    ones = np.asarray(named["llama.layers.3.self_attn.kv_a_layernorm.weight"]
                      ._value, np.float32)
    assert (ones == 1).all()
    again, other = b.build(TINY, 3_000_000_019), b.build(TINY, 5)
    pick = lambda m: np.asarray(dict(m.named_parameters())[     # noqa: E731
        "llama.layers.1.delta.dt_bias"]._value, np.float32)
    assert (pick(again) == pick(tiny_model)).all()
    assert (pick(other) != pick(tiny_model)).any()
    ref_w = b.reference_weights(tiny_model)
    assert set(ref_w) == {"embed", "norm", "head", "layers"}
    assert set(ref_w["layers"][0]) == {
        "n1", "n2", "n3", "n4", "in_proj", "ba_proj", "conv_w", "a_log",
        "dt_bias", "o_norm", "out_proj", "gate", "up", "down"}
    assert {"q_a", "kv_b", "g", "router", "experts_gate_up"} <= \
        set(ref_w["layers"][3])
    with pytest.raises(ValueError, match="n_group"):
        b.build(dict(TINY, n_group=2), 1)
    with pytest.raises(ValueError, match="experts_held lists"):
        b.build(dict(TINY, n_routed_experts=4), 1)


def test_reference_agrees_with_the_model_in_float32_and_catches_a_wrong_decay():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from benchmarks.models import gated_delta_moe_mla as b
    from benchmarks.reference import gated_delta_moe_mla_decoder as ref

    model = b.build(TINY, 11, dtype="float32")
    ids = np.random.default_rng(0).integers(1, 96, 90).tolist()
    with paddle.no_grad():
        got = model(Tensor(jnp.asarray([ids])))._value[0]
    w = b.reference_weights(model)
    assert "slot_states" not in w       # a bare forward pass has no slots
    want = np.asarray(ref.reference_logits(w, BARE, ids))
    assert want.shape == (90, 96)
    res = ref.compare(got, want, 2e-4, 2e-4, margin_eps=0.0,
                      max_left_out_share=0.0)
    assert res["ok"] and res["rows"] == 90, res
    # tight enough to tell a wrong model: one layer's decays doubled
    l1 = w["layers"][1]
    wrong = dict(w, layers=[w["layers"][0],
                            dict(l1, a_log=l1["a_log"] + np.log(2.0))]
                 + w["layers"][2:])
    assert not ref.compare(got, ref.reference_logits(wrong, BARE, ids),
                           2e-4, 2e-4, margin_eps=0.0,
                           max_left_out_share=0.0)["ok"]


# --- a tiny cell end to end on the CPU ---------------------------------------------------------

def test_a_tiny_cell_runs_through_the_launcher(tmp_path):
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-delta.json"), "w") as f:
        json.dump(TINY, f)
    mix = dict(MIX, in_flight=2, lead_in_s=1, trace_s=0.5, cycle=8,
               requests=400,
               output_len={"dist": "lognormal", "median": 60, "sigma": 0.2,
                           "min": 40, "max": 100},
               prompt_len=dict(MIX["prompt_len"], median=40, min=33, max=60))
    with open(os.path.join(bdir, "traffic", "tiny-delta.json"), "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    name = "tiny-delta.tiny-delta"
    bench["configs"].append({"name": "tiny-delta", "source": "test",
                             "reduced": [], "why": "t",
                             "file": "benchmarks/configs/tiny-delta.json"})
    bench["workloads"].append({"name": name, "config": "tiny-delta",
                               "chips": 1, "traffic": "tiny-delta",
                               "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = io.StringIO()
    assert run.run_cell(name, 3_000_000_019, 1.5, True, root=root,
                        platform="cpu", out=out) == 0
    layer = json.loads(out.getvalue().strip().splitlines()[-1])
    assert layer["correct"] and layer["failed"] == 0 and layer["attempted"] > 2
    assert layer["device"]["platform"] == "cpu"
    chk = layer["detail"]["check"]
    assert chk["ok"] and chk["rows"] == 14
    m = layer["metrics"]
    assert m["programs.compiles_in_window.batch"]["value"] == 0
    assert m["cache.preemptions"]["value"] == 0
    assert 0 < m["scheduler.rows_per_step.batch"]["value"] <= 2
    # no device trace on the CPU: the trace readers leave their metrics
    # out; the slots' share is read from the host's phases alone
    assert set(NEW) & set(m) <= {"cache.gdn_slots_peak_share"}


# --- the decode step's state update at the published widths, for the chip that is not attached --

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_mixers_decode_step_compiles_at_the_published_widths(one_chip):
    """The TPU compiler takes ONE delta-rule layer's mixer between its
    projections at the cell's shapes -- 128 rows, 129 slots of 64 x 128 x
    128 float32 and of 3 x 16,384 bf16 -- with the pools donated, updates
    them in place, and keeps what it allocates beside them under 2 GB (the
    gathered states and the new ones; never a second copy of a pool)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gated_delta_moe_mla import (GatedDeltaMoEMLAConfig,
                                                       delta_mixer_core)
    from paddle_tpu.ops.selective_scan import StateCache

    config = GatedDeltaMoEMLAConfig()       # the published widths
    assert config.delta_conv_dim == 16384

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def step(qkvz, ba, conv_w, a_log, dt_bias, o_w, state, conv, slots):
            cache = StateCache(None, None)
            cache.slots = slots
            return delta_mixer_core(config, cache, qkvz, ba, conv_w, a_log,
                                    dt_bias, o_w, state, conv)

        compiled = jax.jit(step, donate_argnums=(6, 7)).lower(
            s((128, 1, 24576)), s((128, 1, 128)), s((4, 16384)),
            s((64,), jnp.float32), s((64,), jnp.float32), s((128,)),
            s((129, 64, 128, 128), jnp.float32), s((129, 3 * 16384)),
            s((128,), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        pools = 129 * rf.state_bytes_per_sequence_layer(GIGA)
        assert mem.alias_size_in_bytes >= pools, mem.alias_size_in_bytes
        assert mem.temp_size_in_bytes < 2e9, mem.temp_size_in_bytes
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
