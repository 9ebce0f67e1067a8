"""The latent-attention / routed-expert configuration's benchmark files:
builder and reference at a tiny size, the traffic mix, the counts of bytes
and operations against hand arithmetic, the four readers on synthetic
traces (the containing-event case among them), and a tiny cell end to end
through the launcher on the CPU.  No TPU library."""

import io
import json
import os
import shutil

import pytest

from benchmarks import harness, moe_mla_spans as spans, roofline_moe_mla as rf
from benchmarks.traffic_kinds import backlog

GLM = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")
MIX = harness.load_json(harness.HERE, "traffic", "reasoning-decode.json")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELL = "glm-4.7-flash.reasoning-decode"
TINY = {"source": "test", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 128, "moe_intermediate_size": 48,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "rope_scaling": None,
        "tie_word_embeddings": False, "q_lora_rank": 32, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 20,
        "n_routed_experts": 8, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 2,
        "routed_scaling_factor": 1.8, "norm_topk_prob": True,
        "first_k_dense_replace": 1, "reduced": {},
        "builder": "glm_moe_mla", "reference": "moe_mla_decoder",
        "engine": {"num_blocks": 64, "block_size": 16,
                   "pool_dtype": "bfloat16", "max_num_seqs": 8,
                   "max_queue": 64, "prefix_cache": False},
        # bf16 at toy widths: scores of 8 experts crowd together, so most
        # rows have a near-tie somewhere; the plumbing is what this checks
        "check": {"prompt_lens": [40, 25], "decode_steps": 4, "atol": 0.05,
                  "rms_rel": 0.08, "margin_eps": 0.004,
                  "max_left_out_share": 0.9}}


# --- the configuration file --------------------------------------------------

def test_published_widths_are_unchanged_and_the_cut_is_stated():
    want = dict(hidden_size=2048, num_attention_heads=20, q_lora_rank=768,
                kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
                v_head_dim=256, n_routed_experts=64, num_experts_per_tok=4,
                moe_intermediate_size=1536, n_shared_experts=1,
                intermediate_size=10240, vocab_size=154880,
                routed_scaling_factor=1.8, first_k_dense_replace=1,
                num_hidden_layers=7, num_nextn_predict_layers=0,
                max_position_embeddings=8192)
    assert {k: GLM[k] for k in want} == want
    assert sorted(GLM["reduced"]) == ["max_position_embeddings",
                                      "num_hidden_layers",
                                      "num_nextn_predict_layers"]
    assert GLM["source"].startswith("https://huggingface.co/zai-org/GLM-4.7-Flash")
    assert GLM["assumed"] and GLM["deployment"]
    check_config_entry(BENCH)
    eng = GLM["engine"]
    assert (eng["num_blocks"], eng["block_size"], eng["max_num_seqs"],
            eng["prefix_cache"]) == (19200, 16, 128, False)


def check_config_entry(bench):
    entry = [c for c in bench["configs"] if c["name"] == "glm-4.7-flash"][0]
    assert sorted(entry["reduced"]) == sorted(GLM["reduced"])


def check_cell_entries(bench):
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"kernels.mla_decode_roofline", "kernels.moe_experts_roofline",
            "programs.moe_overhead_share", "engine.moe_load_max_over_mean",
            "programs.attn_share.batch", "programs.mlp_share.batch",
            "programs.lm_head_share.batch", "kernels.sampler_scope_share.batch",
            "device.idle_share.batch", "engine.gap_fetch_ms.batch",
            "engine.fetch_mb_per_step.batch", "cache.preemptions",
            "cache.pool_peak_share", "device.peak_hbm_gb"} <= listed
    assert not {"kernels.paged_decode_roofline",
                "programs.prefill_flops_share"} & listed
    e2e = {m["name"] for m in harness.Cell(CELL, bench=bench).end_to_end}
    assert e2e == {"tokens_per_s", "setup_s"}


def test_the_cell_lists_the_shared_readers_and_not_the_dense_counts():
    check_cell_entries(BENCH)


# --- bytes and operations against the arithmetic of ISSUE 29 ---------------------

def test_counts_at_the_published_widths():
    assert rf.attention_params(GLM) == 21_757_952 == (
        2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048)
    assert rf.expert_params(GLM) == 9_437_184
    assert rf.expert_bytes(GLM) == 18_874_368
    assert rf.latent_dim(GLM) == 576
    assert rf.latent_bytes_per_token_layer(GLM) == 1_152
    assert rf.latent_bytes_per_token(GLM) == 8_064
    assert rf.expert_layer_params(GLM) == 21_757_952 + 2048 * 64 + 65 * 9_437_184
    assert rf.expert_layer_params(GLM) * 2 / 1e9 == pytest.approx(1.271, abs=1e-3)
    assert rf.dense_layer_params(GLM) * 2 / 1e9 == pytest.approx(0.169, abs=1e-3)
    assert rf.weight_bytes(GLM) / 1e9 == pytest.approx(9.06, abs=0.01)
    pool = GLM["engine"]["num_blocks"] * 16 * rf.latent_bytes_per_token(GLM)
    assert pool / 1e9 == pytest.approx(2.48, abs=0.01)
    assert (rf.weight_bytes(GLM) + pool) / 16e9 > 0.7     # over the size floor


def test_work_of_a_decode_step():
    assert rf.decode_latent_bytes(GLM, 1000) == 1000 * 8064
    assert rf.decode_latent_flops(GLM, 1000) == 2 * 20 * (576 + 512) * 1000 * 7
    assert rf.experts_read_bytes(GLM, 384) == 384 * 18_874_368
    assert rf.experts_flops(GLM, 512 * 6) == 2 * 9_437_184 * 512 * 6
    peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # both kernels are bound by memory at the cell's shapes
    need = rf.roofline_seconds(rf.experts_read_bytes(GLM, 384),
                               rf.experts_flops(GLM, 3072), peaks)
    assert need == pytest.approx(384 * 18_874_368 / 819e9)
    assert rf.roofline_seconds(1.0, 197e12, peaks) == pytest.approx(1.0)


# --- the traffic mix ---------------------------------------------------------------

def test_reasoning_decode_backlog():
    items = backlog.sequence(MIX, 3_000_000_019)
    assert len(items) == 512 and MIX["in_flight"] == 128
    first, rest = items[:128], items[128:]
    assert all(i["section"] == "lead_in" for i in first)
    # 512, not ISSUE 29's 256: the cold set-up passed 1,000 s (PERF.md)
    assert all(512 <= i["prompt_len"] <= 1024
               and 1024 <= i["max_tokens"] <= 3072 for i in rest)
    # the primed wave carries the generated share in its prompt
    assert all(i["prompt_len"] + i["max_tokens"] <= 1024 + 3072
               and i["max_tokens"] >= 1 for i in first)
    assert max(i["prompt_len"] for i in first) > 2048
    assert sum(i["prompt_len"] for i in first) / 128 == pytest.approx(1650, rel=0.1)
    lim = harness.traffic_limits(MIX)
    assert (lim["min_prompt"], lim["max_prompt"], lim["max_total"]) == \
        (512, 1024 + 3071, 4096)
    again = backlog.sequence(MIX, 7)
    assert [i["prompt_len"] for i in again] == [i["prompt_len"] for i in items]


# --- the readers on synthetic traces -------------------------------------------------

DEC, PRE = "jit__decode_fn(3)", "jit__prefill_fn(4)"


def test_an_event_that_contains_others_is_left_out():
    ops = [("while.1", 0.0, 1.0), ("fusion.1", 0.1, 0.3), ("fusion.2", 0.5, 0.4),
           ("copy.3", 1.0, 0.2), ("conditional.4", 2.0, 0.5),
           ("sort.5", 2.0, 0.5)]
    kept = [e[0] for e in spans.leaves(ops)]
    assert kept == ["fusion.1", "fusion.2", "copy.3", "sort.5"]
    assert spans.leaves([]) == []


def test_scope_seconds_by_program_and_sub_scope():
    assert spans.sub_scope_of("jit(_decode_fn)/jit(main)/attn/mla_decode_core/dot") \
        == "mla_decode_core"
    assert spans.sub_scope_of("jit(_decode_fn)/attn/rope") == spans.NONE
    # XLA's grouped-matmul kernel carries no path: it is known by its name
    assert spans.kernel_scope(
        "%ragged-dot-none.11 = bf16[512,3072]{1,0} custom-call(s32[1]{0} %g), "
        "custom_call_target=\"tpu_custom_call\"") == "moe_experts"
    assert spans.kernel_scope("%ragged-dot-metadata.5 = (s32[65]{0}) "
                              "custom-call(s32[64]{0} %x)") == "moe_experts"
    assert spans.kernel_scope("%fusion.5 = bf16[8]{0} fusion(%p)") is None
    rows = {"modules": [(DEC, 0.0, 2.0), (PRE, 3.0, 2.0)],
            "ops": [("gather.1", 0.0, 0.5), ("copy.9", 0.5, 0.1),
                    ("fusion.2", 0.6, 0.2),
                    ("while.7", 1.0, 0.8), ("ragged.3", 1.0, 0.4),
                    ("ragged.4", 1.4, 0.4), ("fusion.5", 1.9, 0.1),
                    ("ragged.3", 3.0, 1.0), ("sortish.6", 4.0, 0.5)]}
    scopes = {"gather.1": "mla_decode_core", "fusion.2": "mla_decode_core",
              "ragged.3": "moe_experts", "ragged.4": "moe_experts",
              "while.7": "moe_experts", "fusion.5": spans.NONE,
              "sortish.6": "moe_dispatch"}
    by = spans.scope_seconds_by_module(rows, scopes)
    # the unnamed copy between two ops of one scope is that scope's; the
    # while's 0.8 s is counted through its body alone
    assert by["jit__decode_fn"] == pytest.approx(
        {"mla_decode_core": 0.8, "moe_experts": 0.8, spans.NONE: 0.1})
    assert by["jit__prefill_fn"] == pytest.approx(
        {"moe_experts": 1.0, "moe_dispatch": 0.5})


def fetch(decode, assignments, touched, max_load):
    return ("engine.fetch", 0.0, 0.1,
            {"bytes": 1, "moe_assignments": assignments, "moe_decode": decode,
             "moe_experts_touched": touched, "moe_max_load": max_load})


def test_the_four_metrics_from_one_synthetic_trace():
    planes = {"/device:TPU:0": {
        "modules": [(DEC, 0.0, 1.0), (DEC, 2.0, 1.0), (PRE, 4.0, 1.0)],
        "ops": [("gather.1", 0.0, 0.004), ("ragged.3", 0.1, 0.018),
                ("top.8", 0.2, 0.001),
                ("gather.1", 2.0, 0.004), ("ragged.3", 2.1, 0.018),
                ("ragged.3", 4.0, 0.5)]}}
    scopes = {"/device:TPU:0": {"gather.1": "mla_decode_core",
                                "ragged.3": "moe_experts",
                                "top.8": "moe_router"}}
    phases = [("engine.dispatch", 0, 0, {}), fetch(1, 3072, 384, 72),
              fetch(1, 3072, 380, 60), fetch(0, 90000, 384, 2000)]
    a = spans.analyse(planes, phases, scopes)
    assert a["routing"] == {"launches": 2, "assignments": 6144,
                            "touched": 764, "max_load": 132}
    assert a["module_launches"] == {"jit__decode_fn": 2.0,
                                    "jit__prefill_fn": 1.0}
    c = {"model": GLM, "engine": GLM["engine"],
         "peaks": {"bytes_per_s": 819e9, "flops_per_s": 197e12},
         "traced": {"probe": {"decode_kv_tokens": 2 * 200_000}}}
    # 400k cached tokens x 8,064 B at 819 GB/s = 3.94 ms over 8 ms
    assert spans.mla_decode_roofline(c, a) == pytest.approx(
        100 * (400_000 * 8064 / 819e9) / 0.008)
    # 764 experts x 18.87 MB at 819 GB/s = 17.6 ms over 36 ms
    assert spans.moe_experts_roofline(c, a) == pytest.approx(
        100 * (764 * 18_874_368 / 819e9) / 0.036)
    assert 0 < spans.moe_experts_roofline(c, a) < 100
    assert spans.moe_overhead_share({"busy_s": 0.5}, a) == pytest.approx(0.2)
    assert spans.moe_load_max_over_mean(c, a) == pytest.approx(132 * 64 / 6144)


def test_a_trace_without_the_scopes_reads_as_nothing():
    planes = {"/device:TPU:0": {"modules": [(DEC, 0.0, 1.0)],
                                "ops": [("fusion.1", 0.0, 0.5)]}}
    assert spans.analyse(planes, [], {"/device:TPU:0": {"fusion.1": spans.NONE}}) is None
    assert spans.analyse({}, [], {}) is None
    c = {"model": GLM, "engine": GLM["engine"], "peaks": {}, "traced": {}}
    assert spans.mla_decode_roofline(c, None) is None
    assert spans.moe_experts_roofline(c, None) is None
    assert spans.moe_overhead_share({"busy_s": 1.0}, None) is None
    assert spans.moe_load_max_over_mean(c, None) is None
    assert spans.analysis(None) is None
    for name in ("kernels.mla_decode_roofline", "kernels.moe_experts_roofline",
                 "programs.moe_overhead_share", "engine.moe_load_max_over_mean"):
        assert harness.load_reader(name).read(c, None) is None


@pytest.mark.skipif(not os.path.exists(os.path.join(
    harness.HERE, "data", "small_trace.xplane.pb")), reason="no recorded trace")
def test_a_recorded_trace_of_a_dense_model_reads_as_nothing():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    assert spans.load(path) is None


# --- builder and reference at a tiny size ----------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmarks.models import glm_moe_mla

    return glm_moe_mla.build(TINY, 3_000_000_019)


def test_builder_serves_bf16_and_a_float32_bias(tiny_model):
    import numpy as np

    from benchmarks.models import glm_moe_mla

    named = dict(tiny_model.named_parameters())
    assert str(named["lm_head.weight"].dtype).endswith("bfloat16")
    bias = named["llama.layers.1.mlp.e_score_correction_bias"]
    assert str(bias.dtype).endswith("float32")
    assert 0.01 < float(np.asarray(bias._value).std()) < 0.12
    again = glm_moe_mla.build(TINY, 3_000_000_019)
    other = glm_moe_mla.build(TINY, 5)
    pick = lambda m: np.asarray(
        dict(m.named_parameters())["llama.layers.2.mlp.w_down"]._value,
        np.float32)
    assert (pick(again) == pick(tiny_model)).all()
    assert (pick(other) != pick(tiny_model)).any()
    w = glm_moe_mla.reference_weights(tiny_model)
    assert "router" not in w["layers"][0] and "gate" in w["layers"][0]
    assert w["layers"][1]["experts_gate_up"].shape == (8, 64, 96)
    with pytest.raises(ValueError, match="rope_scaling"):
        glm_moe_mla.build(dict(TINY, rope_scaling={"type": "yarn"}), 1)
    with pytest.raises(ValueError, match="n_group"):
        glm_moe_mla.build(dict(TINY, n_group=2), 1)


def test_reference_agrees_with_the_model_in_float32():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from benchmarks.models import glm_moe_mla
    from benchmarks.reference import moe_mla_decoder as ref

    model = glm_moe_mla.build(TINY, 11, dtype="float32")
    ids = np.random.default_rng(0).integers(1, 256, 60).tolist()
    with paddle.no_grad():
        got = model(Tensor(jnp.asarray([ids])))._value[0]
    model.pop_expert_load()
    w = glm_moe_mla.reference_weights(model)
    res = ref.compare(got, ref.reference_logits(w, TINY, ids), 1e-4, 1e-4,
                      margin_eps=1e-6, max_left_out_share=0.0)
    assert res["ok"] and res["rows_compared"] == 60, res
    # tight enough to tell a wrong model: a dropped layer, a scaled norm
    for wrong in (dict(w, layers=w["layers"][:2]),
                  dict(w, norm=w["norm"] * 1.25)):
        bad = ref.compare(got, ref.reference_logits(wrong, TINY, ids), 1e-4,
                          1e-4, margin_eps=1e-6, max_left_out_share=0.0)
        assert not bad["ok"]


def test_compare_leaves_out_near_ties_and_bounds_their_share():
    import numpy as np

    from benchmarks.reference import moe_mla_decoder as ref

    want = np.random.default_rng(1).normal(size=(10, 32)).astype(np.float32)
    got = want.copy()
    got[3] += 5.0                        # a row that routed otherwise
    margins = np.full(10, 0.02)
    margins[3] = 1e-4
    res = ref.compare(got, want, 0.1, 0.01, margins=margins, margin_eps=1e-3,
                      max_left_out_share=0.2)
    assert res["ok"] and res["rows_compared"] == 9
    assert res["left_out_share"] == pytest.approx(0.1)
    # the same row with a clear margin is a fault, not a near-tie
    margins[3] = 0.02
    assert not ref.compare(got, want, 0.1, 0.01, margins=margins,
                           margin_eps=1e-3, max_left_out_share=0.2)["ok"]
    # a near-tie that agrees is compared like any other row
    res = ref.compare(want, want, 0.1, 0.01, margins=np.full(10, 1e-4),
                      margin_eps=1e-3, max_left_out_share=0.0)
    assert res["ok"] and res["rows_compared"] == 10
    # too many rows excused as near-ties fail the run
    res = ref.compare(want + 5.0, want, 0.1, 0.01, margins=np.full(10, 1e-4),
                      margin_eps=1e-3, max_left_out_share=0.5)
    assert not res["ok"] and res["rows_compared"] == 0
    # the margins of the calls before it are the default, last rows of each
    ref._SEEN[:] = [np.arange(8.0), np.arange(10.0, 16.0)]
    assert ref.seen_margins(6).tolist() == [5, 6, 7, 13, 14, 15]
    assert ref._SEEN == []


# --- a tiny cell end to end on the CPU ----------------------------------------------------

def test_a_tiny_cell_runs_through_the_launcher(tmp_path):
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-moe.json"), "w") as f:
        json.dump(TINY, f)
    mix = dict(MIX, in_flight=8, lead_in_s=1, trace_s=0.5, cycle=8,
               prompt_len=dict(MIX["prompt_len"], median=24, min=8, max=48),
               output_len=dict(MIX["output_len"], median=16, min=8, max=32))
    with open(os.path.join(bdir, "traffic", "tiny-reasoning.json"), "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    name = "tiny-moe.tiny-reasoning"
    bench["configs"].append({"name": "tiny-moe", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny-moe.json", "why": "t"})
    bench["workloads"].append({"name": name, "config": "tiny-moe", "chips": 1,
                               "traffic": "tiny-reasoning", "why": "t"})
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
    assert chk["ok"] and chk["rows"] == 10 and chk["rows_compared"] >= 1
    assert len(chk["row_margin"]) == 10
    m = layer["metrics"]
    assert m["programs.compiles_in_window.batch"]["value"] == 0
    assert m["cache.preemptions"]["value"] == 0
    assert 0 < m["cache.pool_peak_share"]["value"] <= 100
    assert m["scheduler.rows_per_step.batch"]["value"] > 1
    # no device trace on the CPU: the trace readers leave their metrics out
    assert not {"kernels.mla_decode_roofline", "kernels.moe_experts_roofline",
                "programs.moe_overhead_share",
                "engine.moe_load_max_over_mean"} & set(m)
