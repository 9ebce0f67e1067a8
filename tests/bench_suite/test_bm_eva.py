"""The chunk-summarised-attention (EVA) configuration's benchmark files:
the configuration against the catalog's published keys, the counts of
bytes against hand arithmetic, the traffic mix, the four readers on one
synthetic trace (and silent on a recorded trace of another model), builder
and reference at a tiny size, a tiny cell end to end through the launcher
on the CPU, and one compile of the decode step at the published widths for
a chip that is described and not attached.

The checks of the benchmark's entries are ``check_*(bench)`` functions
(the append contract at the head of ``test_bm_harness.py``): no pin on
last place, no count over a list."""

import io
import json
import os
import shutil

import pytest

from benchmarks import eva_spans as spans, harness, roofline_eva as rf
from benchmarks.traffic_kinds import backlog

EVA = harness.load_json(harness.HERE, "configs", "evabyte-6.5b.json")
MIX = harness.load_json(harness.HERE, "traffic", "byte-reasoning-decode.json")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = "evabyte-6.5b"
CELL = "evabyte-6.5b.byte-reasoning-decode"
OWN = ("kernels.eva_decode_roofline", "programs.eva_attn_share",
       "engine.eva_summary_read_share", "cache.eva_summary_peak_share")
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
DEC, PRE = "jit__decode_fn", "jit__prefill_fn"
# the catalog's ``config`` of the row ``EvaByte`` (model-configs guide,
# ``architectures.jsonl``), as published
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
SERVED = {"num_hidden_layers": 8, "num_pred_heads": 1}
ASSUMED = ("pooling_forms", "pooling_vectors", "aligned_windows",
           "summaries_after_close", "rotate_before_pooling", "scaling",
           "rope_pairing", "stacked_head", "seeded_weights")
TINY = {"source": "test", "vocab_size": 96, "hidden_size": 64,
        "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "window_size": 32, "chunk_size": 16,
        "num_pred_heads": 1, "num_pred_heads_held": 2, "reduced": {},
        "builder": "eva_dense", "reference": "eva_decoder",
        "engine": {"num_blocks": 64, "block_size": 16,
                   "pool_dtype": "bfloat16", "max_num_seqs": 4,
                   "max_queue": 64, "prefix_cache": False},
        # bf16 at toy widths: the plumbing is what this checks
        "check": {"prompt_lens": [70, 12], "decode_steps": 3, "atol": 0.05,
                  "rms_rel": 0.08}}


# --- the configuration file and the benchmark's entries ------------------------------

def test_every_published_key_is_unchanged_but_those_under_reduced():
    assert sorted(EVA["reduced"]) == sorted(SERVED)
    for key, value in PUBLISHED.items():
        assert EVA[key] == SERVED.get(key, value), key
    assert EVA["published"] == {k: PUBLISHED[k] for k in SERVED}
    assert EVA["num_pred_heads_held"] == PUBLISHED["num_pred_heads"]
    assert EVA["source"] == \
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    for key in ASSUMED:
        assert EVA["assumed"][key], key
    assert "3.26 GB" in EVA["deployment"] and "12.39 GB" in EVA["deployment"]
    assert EVA["check"]["why"]
    eng = EVA["engine"]
    assert (eng["num_blocks"], eng["block_size"], eng["pool_dtype"],
            eng["max_num_seqs"], eng["prefix_cache"]) == \
        (17 * 2048, 16, "bfloat16", 16, False)
    # one row's window closes DURING decode, both cross chunk boundaries
    lens, steps = EVA["check"]["prompt_lens"], EVA["check"]["decode_steps"]
    W, C = EVA["window_size"], EVA["chunk_size"]
    assert any(n // W < (n + steps) // W for n in lens)
    assert all(n // C < (n + steps) // C for n in lens)
    check_config_entry(BENCH)


def check_config_entry(bench):
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert sorted(entry["reduced"]) == sorted(EVA["reduced"])
    assert entry["file"] == "benchmarks/configs/evabyte-6.5b.json"
    assert entry["source"] == EVA["source"] and 1 <= len(entry["why"]) <= 200
    names = [c["name"] for c in bench["configs"]]
    # appended: after every configuration that was accepted before it
    assert names.index(CONFIG) > names.index("xing4.0-29b-a4b")


def check_cell_entries(bench):
    row = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (row["config"], row["traffic"], row["chips"]) == \
        (CONFIG, "byte-reasoning-decode", 1)
    assert 1 <= len(row["why"]) <= 200      # the contract's limit on a line
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("xing4.0-29b-a4b.doc-prefill")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(OWN) <= listed
    assert {"scheduler.rows_per_step.batch", "scheduler.padding_share",
            "cache.pool_peak_share", "cache.preemptions",
            "device.peak_hbm_gb", "programs.warm_s_per_program",
            "programs.attn_share.batch", "programs.mlp_share.batch",
            "programs.lm_head_share.batch", "device.idle_share.batch",
            "engine.host_ms_per_step.batch",
            "programs.compiles_in_window.batch"} <= listed
    # not the dense counts, nor what other cells' tests hold to themselves
    assert not {"kernels.paged_decode_roofline",
                "programs.prefill_flops_share",
                "kernels.window_decode_roofline",
                "cache.window_ring_peak_share",
                "cache.state_slots_peak_share",
                "programs.mhc_share"} & listed
    e2e = {m["name"] for m in harness.Cell(CELL, bench=bench).end_to_end}
    assert e2e == {"tokens_per_s", "setup_s"}
    # in every list it shares it stands after the cells accepted before it
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads", ())
        if CELL in ws and len(ws) > 1:
            assert all(ws.index(CELL) > ws.index(w) for w in ws
                       if w in cells[:cells.index(CELL)])


def check_the_four_entries(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in OWN]
    assert at == sorted(at)                 # their order among themselves
    assert at[0] > names.index("engine.hc_clamped_share")   # after PR 43's
    by = {n: bench["per_layer"][names.index(n)] for n in OWN}
    for n in OWN:
        assert by[n]["moves"] == "tokens_per_s"
        assert by[n]["workloads"][0] == CELL and by[n]["unit"] == "%"
    assert [by[n]["better"] for n in OWN] == ["higher", "lower", "higher",
                                              "higher"]
    assert [by[n]["layer"] for n in OWN] == ["kernels", "programs",
                                             "engine host loop", "cache"]


def test_the_cell_and_its_entries_are_appended():
    check_cell_entries(BENCH)
    check_the_four_entries(BENCH)


def test_the_checks_take_one_more_append():
    """A later PR's configuration, cell and metric after this one's: every
    check above still holds (the append contract)."""
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "later", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/later.json",
                             "why": "t"})
    bench["workloads"].append({"name": "later.cell", "config": "later",
                               "traffic": "byte-reasoning-decode",
                               "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("later.cell")
    bench["per_layer"].append({"name": "kernels.later", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels", "moves": "tokens_per_s",
                               "workloads": ["later.cell"]})
    check_config_entry(bench)
    check_cell_entries(bench)
    check_the_four_entries(bench)


# --- bytes against the arithmetic of ISSUE 45 -----------------------------------------------

def test_counts_at_the_served_sizes():
    assert rf.head_dim(EVA) == 128
    assert rf.attention_params(EVA) == 67_117_056 == 4 * 4096 ** 2 + 2 * 4096
    assert rf.mlp_params(EVA) == 135_266_304 == 3 * 4096 * 11008
    assert rf.layer_params(EVA) == 202_391_552
    assert rf.layer_params(EVA) * 2 == 404_783_104          # 404.8 MB
    assert rf.total_params(EVA) == 1_630_932_992 == (
        8 * 202_391_552 + 320 * 4096 + 8 * 320 * 4096 + 4096)
    assert rf.weight_bytes(EVA) == 3_261_865_984            # 3.26 GB
    assert rf.row_bytes(EVA) == 16_384 == 2 * 32 * 128 * 2
    assert rf.ring_bytes_per_sequence(EVA) == 8 * 2048 * 16_384
    assert rf.rows_per_block(EVA, 16) == 1
    assert rf.summary_bytes_per_block(EVA, 16) == 8 * 16_384
    eng = EVA["engine"]
    rings = (eng["max_num_seqs"] + 1) * rf.ring_bytes_per_sequence(EVA)
    rows = eng["num_blocks"] * rf.summary_bytes_per_block(EVA, 16)
    assert rings == rows == 4_563_402_752                   # 4.56 GB each
    held = rf.weight_bytes(EVA) + rings + rows
    assert 12.38e9 < held < 12.40e9 and 0.77 < held / 16e9 < 0.78


def test_work_of_a_decode_step():
    # a row at position p: (p mod 2,048) + 1 ring entries, 128 rows a
    # closed window
    assert (rf.ring_tokens(EVA, 0), rf.summary_rows(EVA, 0)) == (1, 0)
    assert (rf.ring_tokens(EVA, 2047), rf.summary_rows(EVA, 2047)) == (2048, 0)
    assert (rf.ring_tokens(EVA, 2048), rf.summary_rows(EVA, 2048)) == (1, 128)
    assert (rf.ring_tokens(EVA, 32767), rf.summary_rows(EVA, 32767)) == \
        (2048, 15 * 128)
    assert rf.decode_read_bytes(EVA, 2048) == 2048 * 16_384 * 8
    # ISSUE 45's arithmetic: a row reads ~1,024 ring entries and ~1,024
    # summary rows a layer, 33.6 MB; 16 rows 537 MB a layer beside 405 MB
    # of weights: 57% of the step's 7.5 GB
    one = rf.decode_read_bytes(EVA, 2048) / 8
    assert one == 33_554_432
    step = rf.weight_bytes(EVA) + 16 * 8 * one
    assert 7.5e9 < step < 7.6e9 and 0.56 < 16 * 8 * one / step < 0.58
    assert 9.1e-3 < step / 819e9 < 9.3e-3               # 9.2 ms a step
    ps = [1536 + 2000 * i for i in range(16)]
    assert rf.decode_step_bytes(EVA, ps) == rf.weight_bytes(EVA) + sum(
        (p % 2048 + 1 + 128 * (p // 2048)) * 16_384 * 8 for p in ps)


def test_byte_reasoning_decode_backlog():
    assert (MIX["kind"], MIX["in_flight"], MIX["requests"], MIX["cycle"],
            MIX["layout_seed"], MIX["prime_first_wave"]) == \
        ("backlog", 16, 96, 32, 23, True)
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.25, "min": 1024, "max": 2048}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 22528,
                                 "sigma": 0.2, "min": 14336, "max": 30720}
    assert MIX["sampling"] == {"greedy_every": 1} and MIX["stream"]
    assert MIX["trace_s"] == 3.0
    if MIX["lead_in_s"] != 30:
        assert "lead_in_s" in MIX["why_departs"]
    lim = harness.traffic_limits(MIX)
    # the longest sequence is the published positions; the primed first
    # wave's prompts reach them less one
    assert lim["max_total"] == EVA["max_position_embeddings"] == 32768
    assert (lim["min_prompt"], lim["max_prompt"], lim["in_flight"]) == \
        (1024, 32767, 16)
    # every row can reach them: nothing is preempted
    assert (EVA["engine"]["num_blocks"] - 1) * 16 >= 16 * lim["max_total"]
    items = backlog.sequence(MIX, 3_000_000_019)
    assert len(items) == 96
    first = items[:16]
    assert all(i["section"] == "lead_in" for i in first)
    assert all(i["prompt_len"] + i["max_tokens"] <= 32768 for i in items)
    # primed mid-flight: the first wave's rows spread over the positions
    assert min(i["prompt_len"] for i in first) < 6000
    assert max(i["prompt_len"] for i in first) > 20000
    assert items == backlog.sequence(MIX, 3_000_000_019)


# --- the four readers -----------------------------------------------------------------

def test_scope_of_a_path():
    assert spans.scope_of("jit(_decode_fn)/attn/eva_attn/eva_local/dot") == \
        "eva_attn"
    assert spans.scope_of("jit(_decode_fn)/attn/eva_pool/reduce") == "eva_pool"
    assert spans.scope_of("jit(_prefill_fn)/attn/while/body/eva_attn/exp") \
        == "eva_attn"
    assert spans.scope_of("jit(_decode_fn)/attn/dot_general") == spans.NONE
    assert spans.scope_of("jit(_decode_fn)/mlp/dot") == spans.NONE


def build(ring, rows, closed=0, held=0):
    return ("engine.build", 0.0, 0.1, {
        "rows": 16, "state_rows": 16, "state_slots_held": 16,
        "eva_ring_tokens": ring, "eva_summary_rows": rows,
        "eva_windows_closed": closed, "eva_rows_held": held})


def synthetic():
    planes = {"/device:TPU:0": {
        "modules": [(DEC, 0.0, 1.0), (DEC, 2.0, 1.0), (DEC, 4.0, 1.0),
                    (PRE, 6.0, 1.0)],
        "ops": [("ring.1", 0.0, 0.010), ("rows.2", 0.02, 0.010),
                ("pool.3", 0.04, 0.001), ("mlp.4", 0.1, 0.020),
                ("ring.1", 2.0, 0.010), ("rows.2", 2.02, 0.010),
                ("pool.3", 2.04, 0.001),
                ("ring.1", 4.0, 0.010), ("rows.2", 4.02, 0.010),
                ("pool.3", 4.04, 0.001),
                ("while.9", 6.0, 0.05), ("span.5", 6.0, 0.02),
                ("span.5", 6.02, 0.03), ("pool.3", 6.1, 0.002)]}}
    scopes = {"/device:TPU:0": {
        "ring.1": "eva_attn", "rows.2": "eva_attn", "pool.3": "eva_pool",
        "mlp.4": spans.NONE, "while.9": "eva_attn", "span.5": "eva_attn"}}
    phases = [("engine.dispatch", 0, 0, {}),
              build(16_000, 15_360, 0, 17_000),
              build(16_016, 15_360, 1, 17_001),
              ("engine.build", 0.0, 0.1, {"state_rows": 1})]   # a prefill's
    return spans.analyse(planes, phases, scopes)


def test_the_four_metrics_from_one_synthetic_trace():
    a = synthetic()
    assert a["ints"] == {"builds": 2, "ring_tokens": 32_016,
                         "summary_rows": 30_720, "windows_closed": 1,
                         "rows_held_max": 17_001}
    assert a["module_launches"] == {DEC: 3.0, PRE: 1.0}
    assert a["scope_s"][DEC]["eva_attn"] == pytest.approx(0.06)
    # the while's 0.05 s is counted through its body alone
    assert a["scope_s"][PRE]["eva_attn"] == pytest.approx(0.05)
    c = {"model": EVA, "engine": EVA["engine"], "peaks": PEAKS}
    trace = {"busy_s": 0.5}
    # two builds' mean (31,368 rows) x three device launches x 16,384 B x
    # 8 layers at 819 GB/s = 15.06 ms over the 60 ms under eva_attn
    rows = (32_016 + 30_720) / 2 * 3
    assert spans.eva_decode_roofline(c, a) == pytest.approx(
        100 * (rows * 16_384 * 8 / 819e9) / 0.06)
    assert 25.0 < spans.eva_decode_roofline(c, a) < 25.2
    # eva_attn 0.11 s and eva_pool 0.005 s in all programs of 0.5 s busy
    assert spans.eva_attn_share(trace, a) == pytest.approx(100 * 0.115 / 0.5)
    assert spans.eva_summary_read_share(a) == pytest.approx(
        100 * 30_720 / 62_736)
    assert spans.eva_summary_peak_share(c, a) == pytest.approx(
        100 * 17_001 / 34_816)
    # and through the files the harness loads, trace or no trace
    for name in OWN:
        mod = harness.load_reader(name)
        assert mod.read(c, None) is None
        m = [e for e in BENCH["per_layer"] if e["name"] == name][0]
        assert (mod.UNIT, mod.LAYER, mod.SOURCE) == \
            (m["unit"], m["layer"], m["source"])


def test_a_trace_without_the_scopes_reads_as_nothing():
    planes = {"/device:TPU:0": {"modules": [(DEC, 0.0, 1.0)],
                                "ops": [("fusion.1", 0.0, 0.5)]}}
    scopes = {"/device:TPU:0": {"fusion.1": spans.NONE}}
    ring = ("engine.build", 0.0, 0.1, {"rows": 4, "state_rows": 4,
                                       "window_tokens": 99})
    # another model with rings: a sliding window, no rows
    assert spans.analyse(planes, [ring], scopes) is None
    assert spans.analyse({}, [], {}) is None
    c = {"model": EVA, "engine": EVA["engine"], "peaks": PEAKS}
    assert spans.eva_decode_roofline(c, None) is None
    assert spans.eva_attn_share({"busy_s": 1.0}, None) is None
    assert spans.eva_summary_read_share(None) is None
    assert spans.eva_summary_peak_share(c, None) is None
    assert spans.analysis(None) is None
    # nor does another configuration's file read a roofline here
    cmd = harness.load_json(harness.HERE, "configs",
                            "command-a-plus-05-2026.json")
    a = synthetic()
    assert spans.eva_decode_roofline(dict(c, model=cmd), a) is None
    assert spans.eva_summary_peak_share(dict(c, model=cmd), a) is None
    # a traced run in which no decode program ran reads none either
    a["module_launches"] = {PRE: 1.0}
    a["scope_s"].pop(DEC)
    assert spans.eva_decode_roofline(c, a) is None


@pytest.mark.skipif(not os.path.exists(os.path.join(
    harness.HERE, "data", "small_trace.xplane.pb")), reason="no recorded trace")
def test_a_recorded_trace_of_a_dense_model_reads_as_nothing():
    path = os.path.join(harness.HERE, "data", "small_trace.xplane.pb")
    assert spans.load(path) is None


# --- builder and reference at a tiny size --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmarks.models import eva_dense

    return eva_dense.build(TINY, 3_000_000_019)


def test_builder_serves_bf16_and_a_seed_over_31_bits_builds(tiny_model):
    import numpy as np

    from benchmarks.models import eva_dense

    named = dict(tiny_model.named_parameters())
    assert all(str(p.dtype).endswith("bfloat16") for p in named.values())
    # the stacked head is held whole, head 0 is what the program computes
    assert named["lm_head.weight"].shape == [64, 2 * 96]
    mu = np.asarray(named["llama.layers.0.self_attn.adaptive_mu_k"]._value,
                    np.float32)
    assert mu.shape == (2, 32) and 0.08 < mu.std() < 0.3     # 32 ** -0.5
    q = np.asarray(named["llama.layers.1.self_attn.q_proj.weight"]._value,
                   np.float32)
    assert 0.015 < q.std() < 0.025
    for name in ("llama.norm.weight", "llama.layers.0.input_layernorm.weight"):
        assert float(np.abs(np.asarray(named[name]._value,
                                       np.float32)).max()) == 0.0
    again = eva_dense.build(TINY, 3_000_000_019)
    other = eva_dense.build(TINY, 5)
    pick = lambda m: np.asarray(dict(m.named_parameters())[
        "llama.layers.1.self_attn.adaptive_phi"]._value, np.float32)
    assert (pick(again) == pick(tiny_model)).all()
    assert (pick(other) != pick(tiny_model)).any()
    w = eva_dense.reference_weights(tiny_model)
    assert set(w) == {"embed", "norm", "head", "layers"}
    assert set(w["layers"][0]) == {"norm1", "q", "k", "v", "o", "mu", "phi",
                                   "norm2", "gate", "up", "down"}
    with pytest.raises(ValueError, match="attention_class"):
        eva_dense.build(dict(TINY, attention_class="softmax"), 1)
    with pytest.raises(ValueError, match="fp32_ln"):
        eva_dense.build(dict(TINY, fp32_ln=True), 1)


def test_reference_agrees_with_the_model_in_float32_and_is_independent():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from benchmarks.models import eva_dense
    from benchmarks.reference import eva_decoder as ref

    model = eva_dense.build(TINY, 11, dtype="float32")
    ids = np.random.default_rng(0).integers(1, 96, 90).tolist()
    with paddle.no_grad():
        got = model(Tensor(jnp.asarray([ids])))._value[0]
    w = eva_dense.reference_weights(model)
    want = np.asarray(ref.reference_logits(w, TINY, ids))
    assert want.shape == (90, 96)
    res = ref.compare(got, want, 1e-4, 1e-4)
    assert res["ok"] and res["rows"] == 90, res
    # tight enough to tell a wrong model: the two pooling vectors swapped
    # in one layer
    l0 = w["layers"][0]
    wrong = dict(w, layers=[dict(l0, mu=l0["phi"], phi=l0["mu"])]
                 + w["layers"][1:])
    assert not ref.compare(got, ref.reference_logits(wrong, TINY, ids),
                           1e-4, 1e-4)["ok"]
    # heads 1.. of the stacked head do not reach the next-byte logits
    other = dict(w, head=w["head"].at[:, 96:].set(0.0))
    np.testing.assert_array_equal(
        np.asarray(ref.reference_logits(other, TINY, ids)), want)
    src = open(ref.__file__).read()
    assert "paddle_tpu" not in src.replace("``paddle_tpu", "")
    assert 'default_matmul_precision("highest")' in src


# --- a tiny cell end to end on the CPU ---------------------------------------------------------

def test_a_tiny_cell_runs_through_the_launcher(tmp_path):
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-eva.json"), "w") as f:
        json.dump(TINY, f)
    # a primed first wave: prompts of up to 5 windows, then decode through
    # window ends; three prefill buckets, two row buckets, ONE table width
    mix = dict(MIX, in_flight=2, lead_in_s=1, trace_s=0.5, cycle=8,
               requests=400,
               output_len={"dist": "lognormal", "median": 60, "sigma": 0.2,
                           "min": 40, "max": 100},
               prompt_len=dict(MIX["prompt_len"], median=40, min=33, max=60))
    with open(os.path.join(bdir, "traffic", "tiny-bytes.json"), "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    name = "tiny-eva.tiny-bytes"
    bench["configs"].append({"name": "tiny-eva", "source": "test",
                             "reduced": [], "why": "t",
                             "file": "benchmarks/configs/tiny-eva.json"})
    bench["workloads"].append({"name": name, "config": "tiny-eva", "chips": 1,
                               "traffic": "tiny-bytes", "why": "t"})
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
    assert chk["ok"] and chk["rows"] == 8
    # the decode programs have ONE table width: the positions' (256 / 16)
    split = layer["detail"]["setup_split"]
    assert split["warm_programs"] == 3 + 2
    m = layer["metrics"]
    assert m["programs.compiles_in_window.batch"]["value"] == 0
    assert m["cache.preemptions"]["value"] == 0
    assert 0 < m["cache.pool_peak_share"]["value"] <= 100
    assert 0 < m["scheduler.rows_per_step.batch"]["value"] <= 2
    # no device trace on the CPU: the trace readers leave their metrics out
    assert not set(OWN) & set(m)


# --- the decode step at the published widths, for the chip that is not attached ------------

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


def test_the_decode_attention_compiles_at_the_published_widths(one_chip):
    """The TPU compiler takes ONE layer's decode attention at the cell's
    shapes -- 16 rows, 17 rings of 2,048 x 32 x 128, 34,816 summary rows, a
    table 2,048 wide -- with the pools donated, and keeps what it allocates
    beside them under 1 GB (scores and the mask; no copy of rings or rows)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import eva_attention as eva

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        ring, rows = s((17, 2048, 32, 128)), s((34816, 1, 32, 128))

        def step(q, kr, vr, krow, vrow, slots, tables, pos):
            o = eva.decode_attention(q, kr, vr, krow, vrow, slots, tables,
                                     pos, 2048, 16)
            return o, kr, vr, krow, vrow

        compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4)).lower(
            s((16, 32, 128)), ring, ring, rows, rows, s((16,), jnp.int32),
            s((16, 2048), jnp.int32), s((16,), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 1e9, mem.temp_size_in_bytes
        assert mem.alias_size_in_bytes >= 2 * (17 * 2048 + 34816) * 8192
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
