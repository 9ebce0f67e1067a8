"""The selective-scan / attention hybrid configuration's benchmark files:
builder and reference at a tiny size, the traffic mix, the counts of
parameters and bytes against hand arithmetic, the four readers on synthetic
traces (the containing-event case among them), and a tiny cell end to end
through the launcher on the CPU.  No TPU library."""

import io
import json
import os
import shutil

import pytest

from benchmarks import harness, roofline_ssm as rf, ssm_spans as spans
from benchmarks.traffic_kinds import backlog

JAMBA = harness.load_json(harness.HERE, "configs", "ai21-jamba2-3b.json")
MIX = harness.load_json(harness.HERE, "traffic", "reasoning-decode-256.json")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELL = "ai21-jamba2-3b.reasoning-decode-256"
NEW = ("kernels.ssm_decode_roofline", "kernels.ssm_scan_roofline",
       "programs.ssm_share", "cache.state_slots_peak_share")
# the ten ``engine.gap_*`` / ``scheduler.gap_*`` entries of the batch cells
GAPS = {f"{stem}.batch" for stem in (
    "engine.gap_intake_ms", "scheduler.gap_plan_ms", "engine.gap_admit_ms",
    "engine.gap_build_ms", "engine.gap_dispatch_ms", "engine.gap_fetch_ms",
    "engine.gap_emit_ms", "engine.gap_trackers_ms",
    "engine.gap_unattributed_share", "engine.gap_offset_width_ms")}
PEAKS = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
TINY = {"source": "test", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 6,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "attn_layer_period": 3,
        "attn_layer_offset": 1, "mamba_d_state": 8, "mamba_d_conv": 4,
        "mamba_dt_rank": 8, "mamba_expand": 2, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "num_experts": 1, "sliding_window": None,
        "reduced": {}, "builder": "jamba_hybrid",
        "reference": "jamba_hybrid_decoder",
        "engine": {"num_blocks": 160, "block_size": 16,
                   "pool_dtype": "bfloat16", "max_num_seqs": 8,
                   "max_queue": 64, "prefix_cache": False},
        "check": {"prompt_lens": [40, 25], "decode_steps": 6, "atol": 0.05,
                  "rms_rel": 0.08}}


# --- the configuration file and the benchmark's entries ------------------------------

def test_every_published_key_is_unchanged_and_nothing_is_cut():
    catalog = dict(
        attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
        expert_layer_period=2, hidden_act="silu", hidden_size=2560,
        intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
        mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
        mamba_proj_bias=False, max_position_embeddings=262144,
        model_type="jamba", num_attention_heads=20, num_experts=1,
        num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
        num_logits_to_keep=1, rms_norm_eps=1e-06, sliding_window=None,
        tie_word_embeddings=True, use_mamba_kernels=True, vocab_size=65536)
    assert {k: JAMBA[k] for k in catalog} == catalog
    assert JAMBA["reduced"] == {}
    check_config_entry(BENCH)
    assert JAMBA["deployment"] and {"head_dim", "layer_order", "state_dtype",
                                    "seeded_recurrence"} <= set(JAMBA["assumed"])
    eng = JAMBA["engine"]
    assert (eng["num_blocks"], eng["block_size"], eng["max_num_seqs"],
            eng["max_queue"], eng["prefix_cache"], eng["pool_dtype"]) == \
        (65792, 16, 256, 512, False, "bfloat16")
    chk = JAMBA["check"]
    assert chk["prompt_lens"] == [300, 300] and chk["decode_steps"] == 64
    assert (JAMBA["builder"], JAMBA["reference"]) == \
        ("jamba_hybrid", "jamba_hybrid_decoder")


def check_config_entry(bench):
    entry = [c for c in bench["configs"] if c["name"] == "ai21-jamba2-3b"][0]
    assert entry["reduced"] == [] and entry["source"] == JAMBA["source"] == \
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"


def check_cell_entries(bench):
    """The cell's entries in ``bench``: membership, and no count of names
    a later PR's entries would move."""
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
            "kernels.sampler_scope_share.batch",
            "programs.warm_s_per_program"} <= listed
    assert GAPS <= listed
    assert not {"kernels.paged_decode_roofline", "programs.prefill_flops_share",
                "kernels.mla_decode_roofline"} & listed
    warm = [m for m in bench["per_layer"]
            if m["name"] == "programs.warm_s_per_program"][0]
    assert warm["workloads"] == [w["name"] for w in bench["workloads"]]
    cell = harness.Cell(CELL, bench=bench)
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic == MIX and cell.config == JAMBA
    for m in cell.per_layer:                # every entry has a reader
        assert callable(cell.reader(m["name"]).read), m["name"]
    for name in NEW:                        # the new ones only here
        entry = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        reader = harness.load_reader(name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE) == \
            (entry["unit"], entry["layer"], entry["source"])


def test_the_cell_lists_the_shared_readers_and_not_the_dense_counts():
    check_cell_entries(BENCH)


# --- parameters and bytes against the arithmetic of ISSUE 33 -------------------------

def test_counts_at_the_published_widths():
    assert rf.d_inner(JAMBA) == 5120 and rf.head_dim(JAMBA) == 128
    assert [i for i in range(28) if rf.is_attention_layer(JAMBA, i)] == [7, 21]
    assert (rf.mixer_layers(JAMBA), rf.attention_layers(JAMBA)) == (26, 2)
    assert rf.mixer_params(JAMBA) == 41_241_792 == (
        2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 192 + 81920 + 5120 + 5120 * 2560)
    assert rf.swiglu_params(JAMBA) == 62_914_560
    assert rf.mixer_layer_params(JAMBA) == 104_161_472
    assert rf.attention_layer_params(JAMBA) == 76_682_240
    assert rf.total_params(JAMBA) == 3_029_337_472
    assert rf.weight_bytes(JAMBA) / 1e9 == pytest.approx(6.06, abs=0.005)
    assert rf.state_bytes_per_sequence_layer(JAMBA) == 358_400 == \
        5120 * 16 * 4 + 3 * 5120 * 2
    assert rf.state_bytes_per_sequence(JAMBA) == 9_318_400
    assert rf.kv_bytes_per_token(JAMBA) == 1_024
    eng = JAMBA["engine"]
    slots = (eng["max_num_seqs"] + 1) * rf.state_bytes_per_sequence(JAMBA)
    pages = eng["num_blocks"] * 16 * rf.kv_bytes_per_token(JAMBA)
    assert slots / 1e9 == pytest.approx(2.39, abs=0.01)
    assert pages / 1e9 == pytest.approx(1.08, abs=0.01)
    assert eng["num_blocks"] == 257 * 256     # every row can reach 4,096 tokens
    held = rf.weight_bytes(JAMBA) + slots + pages
    assert held / 1e9 == pytest.approx(9.53, abs=0.01) and held / 16e9 > 0.59
    untied = dict(JAMBA, tie_word_embeddings=False)
    assert rf.total_params(untied) - rf.total_params(JAMBA) == 65536 * 2560


def test_work_of_a_decode_step_and_of_a_prefill_scan():
    # 256 real rows: every row's state of 26 layers read once and written once
    assert rf.decode_state_bytes(JAMBA, 256) == 2 * 256 * 9_318_400
    assert rf.decode_state_bytes(JAMBA, 256) / 1e9 == pytest.approx(4.77, abs=0.01)
    assert rf.roofline_seconds(rf.decode_state_bytes(JAMBA, 256), PEAKS) == \
        pytest.approx(5.83e-3, rel=1e-2)
    # x and dt read, y written: 30,720 B a token a layer; one state a prompt
    assert rf.scan_bytes(JAMBA, 0, 1) == 26 * 30_720
    assert rf.scan_bytes(JAMBA, 2, 3000) == 26 * (3000 * 30_720 + 2 * 358_400)
    assert rf.scan_bytes(JAMBA, 1, 0, itemsize=4) == 26 * (5120 * 16 * 4
                                                           + 3 * 5120 * 4)


# --- the traffic mix -------------------------------------------------------------------

def test_reasoning_decode_256_backlog():
    items = backlog.sequence(MIX, 3_000_000_019)
    assert len(items) == 1024 and MIX["in_flight"] == 256
    assert (MIX["kind"], MIX["lead_in_s"], MIX["cycle"], MIX["layout_seed"],
            MIX["trace_s"], MIX["stream"]) == ("backlog", 75, 32, 23, 2.0, True)
    first, rest = items[:256], items[256:]
    assert all(i["section"] == "lead_in" for i in first)
    assert all(512 <= i["prompt_len"] <= 1024
               and 1024 <= i["max_tokens"] <= 3072 for i in rest)
    # the primed wave carries the generated share in its prompt
    assert all(i["prompt_len"] + i["max_tokens"] <= 1024 + 3072
               and i["max_tokens"] >= 1 for i in first)
    assert max(i["prompt_len"] for i in first) > 2048
    assert sum(i["prompt_len"] for i in first) / 256 == pytest.approx(1650, rel=0.1)
    assert all(i["greedy"] for i in items)
    lim = harness.traffic_limits(MIX)
    assert (lim["min_prompt"], lim["max_prompt"], lim["max_total"],
            lim["in_flight"]) == (512, 1024 + 3071, 4096, 256)
    # twice the latent cell's concurrency, its lengths otherwise (and since
    # PR 42 a traced slice of 2 s for its 3: the trace's size, PERF.md)
    half = harness.load_json(harness.HERE, "traffic", "reasoning-decode.json")
    same = ("kind", "prompt_len", "output_len", "sampling", "stream", "cycle",
            "layout_seed", "prime_first_wave")
    assert {k: MIX[k] for k in same} == {k: half[k] for k in same}
    assert (MIX["in_flight"], MIX["requests"]) == \
        (2 * half["in_flight"], 2 * half["requests"])


# --- the readers on synthetic traces -----------------------------------------------------

DEC, PRE = "jit__decode_fn(3)", "jit__prefill_fn(4)"


def build(rows, held):
    return ("engine.build", 0.0, 0.1,
            {"rows": rows, "state_rows": rows, "state_slots_held": held})


def test_sub_scope_anywhere_on_the_path_and_the_outer_scope():
    assert spans.sub_scope_of(
        "jit(_decode_fn)/jit(main)/ssm/mamba_mixer/ssm_step/mul") == "ssm_step"
    assert spans.sub_scope_of("jit(_prefill_fn)/ssm/ssm_scan/while/body/exp") \
        == "ssm_scan"
    assert spans.sub_scope_of("jit(_decode_fn)/ssm/rms_norm/mul") == spans.OUTER
    assert spans.sub_scope_of("jit(_decode_fn)/attn/dot") == spans.NONE
    assert spans.sub_scope_of("jit(_decode_fn)/mlp/dot") == spans.NONE


def test_a_scan_is_a_while_and_is_counted_through_its_body_alone():
    rows = {"modules": [(PRE, 0.0, 3.0), (DEC, 4.0, 1.0)],
            "ops": [("fusion.1", 0.0, 0.2),                      # in-projection
                    ("while.7", 1.0, 1.0), ("fusion.2", 1.0, 0.5),
                    ("fusion.3", 1.5, 0.5),                      # the scan's body
                    ("scatter.4", 2.0, 0.1), ("fusion.9", 2.5, 0.25),
                    ("gather.5", 4.0, 0.3), ("fusion.6", 4.3, 0.2)]}
    scopes = {"fusion.1": "ssm_in_proj", "while.7": "ssm_scan",
              "fusion.2": "ssm_scan", "fusion.3": "ssm_scan",
              "scatter.4": "ssm_scan", "fusion.9": spans.NONE,
              "gather.5": "ssm_step", "fusion.6": "ssm_step"}
    a = spans.analyse({"/device:TPU:0": rows}, [], {"/device:TPU:0": scopes})
    assert a["scope_s"]["jit__prefill_fn"] == pytest.approx(
        {"ssm_in_proj": 0.2, "ssm_scan": 1.1, spans.NONE: 0.25})
    assert a["scope_s"]["jit__decode_fn"] == pytest.approx({"ssm_step": 0.5})
    assert a["slots"] is None
    assert spans.scope_s(a, "ssm_scan", "jit__prefill_fn") == pytest.approx(1.1)
    assert spans.scope_s(a, "ssm_scan") == pytest.approx(1.1)
    assert spans.scope_s(a, "ssm_step", "jit__prefill_fn") == 0.0


def test_the_four_metrics_from_one_synthetic_trace():
    planes = {"/device:TPU:0": {
        "modules": [(DEC, 0.0, 1.0), (DEC, 2.0, 1.0), (PRE, 4.0, 1.0)],
        "ops": [("gather.1", 0.0, 0.010), ("fusion.2", 0.1, 0.012),
                ("norm.8", 0.2, 0.001),
                ("gather.1", 2.0, 0.010), ("fusion.2", 2.1, 0.012),
                ("while.7", 4.0, 0.060), ("body.3", 4.0, 0.060),
                ("dot.4", 4.5, 0.1)]}}
    scopes = {"/device:TPU:0": {"gather.1": "ssm_step", "fusion.2": "ssm_step",
                                "norm.8": spans.OUTER, "while.7": "ssm_scan",
                                "body.3": "ssm_scan", "dot.4": spans.NONE}}
    phases = [("engine.dispatch", 0, 0, {}), build(250, 255), build(251, 256),
              ("engine.build", 0, 0, {"state_rows": 1, "state_slots_held": 254})]
    a = spans.analyse(planes, phases, scopes)
    assert a["slots"] == {"launches": 3, "held_max": 256, "rows": 502}
    c = {"model": JAMBA, "engine": JAMBA["engine"], "peaks": PEAKS,
         "traced": {"probe": {"decode_rows": 501, "prefill_launches": 1,
                              "prefill_tokens": 1600}}}
    # 501 rows x 2 x 9,318,400 B at 819 GB/s = 11.4 ms over 44 ms
    assert spans.ssm_decode_roofline(c, a) == pytest.approx(
        100 * (501 * 2 * 9_318_400 / 819e9) / 0.044)
    # 1,600 tokens x 26 x 30,720 B + one state at 819 GB/s = 1.57 ms over 60
    assert spans.ssm_scan_roofline(c, a) == pytest.approx(
        100 * (26 * (1600 * 30_720 + 358_400) / 819e9) / 0.060)
    assert 0 < spans.ssm_scan_roofline(c, a) < spans.ssm_decode_roofline(c, a) < 100
    assert spans.ssm_share({"busy_s": 0.5}, a) == pytest.approx(
        100 * (0.044 + 0.001 + 0.060) / 0.5)
    assert spans.state_slots_peak_share(c, a) == pytest.approx(100.0)
    for name, want in zip(NEW, (spans.ssm_decode_roofline(c, a),
                                spans.ssm_scan_roofline(c, a))):
        assert want is not None


def test_a_trace_without_the_scopes_reads_as_nothing():
    planes = {"/device:TPU:0": {"modules": [(DEC, 0.0, 1.0)],
                                "ops": [("fusion.1", 0.0, 0.5)]}}
    none = {"/device:TPU:0": {"fusion.1": spans.NONE}}
    assert spans.analyse(planes, [], none) is None
    assert spans.analyse(planes, [("engine.build", 0, 0, {"rows": 4})], none) \
        is None
    assert spans.analyse({}, [], {}) is None
    c = {"model": JAMBA, "engine": JAMBA["engine"], "peaks": {}, "traced": {}}
    assert spans.ssm_decode_roofline(c, None) is None
    assert spans.ssm_scan_roofline(c, None) is None
    assert spans.ssm_share({"busy_s": 1.0}, None) is None
    assert spans.state_slots_peak_share(c, None) is None
    assert spans.analysis(None) is None
    for name in NEW:
        assert harness.load_reader(name).read(c, None) is None
    # the slot integers alone (a CPU run: no device plane) still read
    a = spans.analyse(planes, [build(3, 5)], none)
    assert a["slots"]["held_max"] == 5
    assert spans.state_slots_peak_share(c, a) == pytest.approx(100 * 5 / 256)
    assert spans.ssm_decode_roofline(c, a) is None
    # a dense model under the mixer's scopes would read nothing
    mistral = harness.load_json(harness.HERE, "configs", "mistral-7b-v0.3.json")
    dense = dict(c, model=mistral, peaks=PEAKS,
                 traced={"probe": {"decode_rows": 9}})
    ssm = spans.analyse(planes, [], {"/device:TPU:0": {"fusion.1": "ssm_step"}})
    assert spans.ssm_decode_roofline(dense, ssm) is None


@pytest.mark.skipif(not os.path.exists(os.path.join(
    harness.HERE, "data", "small_trace.xplane.pb")), reason="no recorded trace")
def test_a_recorded_trace_of_a_dense_model_reads_as_nothing():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    assert spans.load(path) is None


# --- builder and reference at a tiny size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmarks.models import jamba_hybrid

    return jamba_hybrid.build(TINY, 3_000_000_019)


def test_builder_serves_bf16_and_a_float32_recurrence(tiny_model):
    import numpy as np

    from benchmarks.models import jamba_hybrid

    named = dict(tiny_model.named_parameters())
    dt = lambda n: str(named[n].dtype)
    assert dt("llama.embed_tokens.weight").endswith("bfloat16")
    assert "lm_head.weight" not in named                       # tied
    assert dt("llama.layers.0.mamba.in_proj.weight").endswith("bfloat16")
    assert dt("llama.layers.0.mamba.A_log").endswith("float32")
    assert dt("llama.layers.0.mamba.D").endswith("float32")
    assert "llama.layers.1.self_attn.q_proj.weight" in named   # i % 3 == 1
    assert "llama.layers.1.mamba.A_log" not in named
    a_log = np.asarray(named["llama.layers.2.mamba.A_log"]._value)
    assert a_log.shape == (8, 128)
    np.testing.assert_allclose(np.exp(a_log[:, 5]), np.arange(1, 9), rtol=1e-6)
    bias = np.asarray(named["llama.layers.0.mamba.dt_proj.bias"]._value,
                      np.float32)
    step = np.log1p(np.exp(bias))                 # softplus: 0.001 .. 0.1
    assert 0.0009 < step.min() and step.max() < 0.11
    assert step.max() / step.min() > 10           # log-uniform, not constant
    conv = np.asarray(named["llama.layers.0.mamba.conv_weight"]._value,
                      np.float32)
    assert conv.shape == (4, 128) and 0.2 < np.abs(conv).mean() < 0.3
    again = jamba_hybrid.build(TINY, 3_000_000_019)
    other = jamba_hybrid.build(TINY, 5)
    pick = lambda m: np.asarray(dict(m.named_parameters())[
        "llama.layers.3.mamba.x_proj.weight"]._value, np.float32)
    assert (pick(again) == pick(tiny_model)).all()
    assert (pick(other) != pick(tiny_model)).any()
    w = jamba_hybrid.reference_weights(tiny_model)
    assert [("q" in l, "in_proj" in l) for l in w["layers"]] == [
        (False, True), (True, False), (False, True)] * 2
    assert w["layers"][0]["a_log"] is named["llama.layers.0.mamba.A_log"]._value
    for bad, word in ((dict(num_experts=4), "num_experts"),
                      (dict(sliding_window=128), "sliding_window"),
                      (dict(mamba_proj_bias=True), "mamba_proj_bias")):
        with pytest.raises(ValueError, match=word):
            jamba_hybrid.build(dict(TINY, **bad), 1)


def test_a_seed_over_31_bits_builds(tiny_model):
    from benchmarks.models import jamba_hybrid

    big = jamba_hybrid.build(TINY, 2 ** 31 + 12345)
    assert big.config.num_hidden_layers == 6


def test_reference_agrees_with_the_model_in_float32_and_tells_a_wrong_one():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from benchmarks.models import jamba_hybrid
    from benchmarks.reference import jamba_hybrid_decoder as ref

    model = jamba_hybrid.build(TINY, 11, dtype="float32")
    ids = np.random.default_rng(0).integers(1, 256, 60).tolist()
    with paddle.no_grad():
        got = model(Tensor(jnp.asarray([ids])))._value[0]
    w = jamba_hybrid.reference_weights(model)
    res = ref.compare(got, ref.reference_logits(w, TINY, ids), 1e-4, 1e-4)
    assert res["ok"] and res["rows"] == 60 and res["argmax_agree"] == 1.0, res
    # tight enough to tell a wrong model: a dropped layer, a scaled norm,
    # the layer order read one off
    layer0 = dict(w["layers"][0], d=w["layers"][0]["d"] * 0.0)
    for wrong, m in ((dict(w, layers=w["layers"][:5]), TINY),
                     (dict(w, norm=w["norm"] * 1.25), TINY),
                     (dict(w, layers=[layer0] + w["layers"][1:]), TINY)):
        bad = ref.compare(got, ref.reference_logits(wrong, m, ids), 1e-4, 1e-4)
        assert not bad["ok"]
    with pytest.raises(KeyError):           # kinds come from the keys HERE
        ref.reference_logits(w, dict(TINY, attn_layer_offset=2), ids)


def test_compare_holds_both_limits():
    import numpy as np

    from benchmarks.reference import jamba_hybrid_decoder as ref

    want = np.random.default_rng(1).normal(size=(10, 32)).astype(np.float32)
    assert ref.compare(want, want, 0.1, 0.01)["ok"]
    spike = want.copy()
    spike[3, 4] += 0.5                       # one logit: atol alone catches it
    res = ref.compare(spike, want, 0.1, 0.1)
    assert not res["ok"] and res["rms_rel"] < 0.1 < res["max_abs_diff"]
    drift = want + 0.05 * np.sign(want)      # everywhere: rms_rel alone
    res = ref.compare(drift, want, 0.1, 0.01)
    assert not res["ok"] and res["max_abs_diff"] < 0.1 and res["rms_rel"] > 0.01
    nan = want.copy()
    nan[0, 0] = np.nan
    assert not ref.compare(nan, want, 1e9, 1e9)["ok"]


# --- a tiny cell end to end on the CPU -----------------------------------------------------

def test_a_tiny_cell_runs_through_the_launcher(tmp_path):
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-ssm.json"), "w") as f:
        json.dump(TINY, f)
    mix = dict(MIX, in_flight=8, lead_in_s=1, trace_s=0.5, cycle=8,
               prompt_len=dict(MIX["prompt_len"], median=24, min=8, max=48),
               output_len=dict(MIX["output_len"], median=16, min=8, max=32))
    with open(os.path.join(bdir, "traffic", "tiny-reasoning.json"), "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    name = "tiny-ssm.tiny-reasoning"
    bench["configs"].append({"name": "tiny-ssm", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny-ssm.json", "why": "t"})
    bench["workloads"].append({"name": name, "config": "tiny-ssm", "chips": 1,
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
    assert chk["ok"] and chk["rows"] == 14 and chk["decode_steps"] == 6
    m = layer["metrics"]
    assert m["programs.compiles_in_window.batch"]["value"] == 0
    assert m["cache.preemptions"]["value"] == 0
    assert 0 < m["cache.pool_peak_share"]["value"] <= 100
    assert m["scheduler.rows_per_step.batch"]["value"] > 1
    assert m["programs.warm_s_per_program"]["value"] > 0
    # no device trace on the CPU: the trace readers leave their metrics out
    assert not set(NEW) & set(m)
