"""The harness is driven by data: every name in BENCHMARK.json resolves to
a file of its own, a later PR adds a cell by adding files only, the
reference agrees with the model, and the command line measures on a TPU or
not at all.

**The append contract, stated once for every test in this directory.**  A
later PR grows the benchmark by APPENDING and edits nothing that is there:
a configuration goes to the end of ``configs``; a cell to the end of
``workloads`` and to the END of every shared list it reports (an
end-to-end metric's ``workloads``, a per-layer entry's); per-layer entries
to the END of ``per_layer``.  So a test written for one PR's additions may
pin their order among themselves and against what was accepted BEFORE them
(the names that stand before them, by name), and membership; it never
asserts that they are last (``[-1]``, ``[-n:]``) nor a count of names over
``configs``, ``workloads``, ``per_layer`` or a metric's ``workloads``: the
next PR's entries would fail it, and that PR may not edit this directory.
Each file's checks of the benchmark's entries are functions of a benchmark
given as a ``dict``, named ``check_*`` and taking ``bench`` alone: a later
PR's test file writes its own so.  :func:`test_the_tests_take_an_append`
finds them in every ``test_bm_*.py`` here and runs them all on
``BENCHMARK.json`` as it is, on a copy a later PR could have made, and on
copies with a fault planted."""

import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness, records

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
TINY = {"source": "test", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "builder": "llama_dense", "reference": "dense_decoder",
        "engine": {"num_blocks": 64, "block_size": 16,
                   "pool_dtype": "bfloat16", "max_num_seqs": 8,
                   "max_queue": 64, "prefix_cache": True},
        "check": {"prompt_lens": [40, 25], "decode_steps": 4,
                  "atol": 0.05, "rms_rel": 0.05}}


def check_per_layer_entry(bench, m):
    mod = harness.load_reader(m["name"])
    assert (mod.UNIT, mod.LAYER, mod.SOURCE) == \
        (m["unit"], m["layer"], m["source"])
    moved = [e for e in bench["end_to_end"] if e["name"] == m["moves"]][0]
    cells = set(m.get("workloads") or [w["name"] for w in bench["workloads"]])
    assert cells <= set(moved.get("workloads")
                        or [w["name"] for w in bench["workloads"]])
    assert callable(mod.read)


def check_end_to_end_entry(bench, m):
    assert callable(harness.load_module("e2e_metrics", m["name"]).compute)
    assert 0.01 <= m["bound"] <= 0.1 and m["source"] == "host_clock"


def check_cell(bench, w):
    cell = harness.Cell(w["name"], bench=bench)
    assert cell.config["reduced"].keys() == set(
        [c for c in bench["configs"] if c["name"] == w["config"]][0]["reduced"])
    for kind, name in (("traffic_kinds", cell.traffic["kind"]),
                       ("models", cell.config["builder"]),
                       ("reference", cell.config["reference"])):
        assert cell.module(kind, name)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    lim = harness.traffic_limits(cell.traffic)
    pool = (cell.config["engine"]["num_blocks"] - 1) * 16
    assert lim["max_total"] <= min(pool, cell.config["max_position_embeddings"])


def check_every_reader_has_an_entry(bench):
    """Every ``layer_metrics/*.py`` reads some entry: the entry of its
    name, or entries of its name with a group of cells for a suffix."""
    files = {f[:-3] for f in os.listdir(os.path.join(harness.HERE,
                                                     "layer_metrics"))
             if f.endswith(".py")}
    names = {m["name"] for m in bench["per_layer"]}
    stems = names | {n.rpartition(".")[0] for n in names}
    assert files <= stems, sorted(files - stems)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_is_a_file_that_agrees_with_its_entry(m):
    check_per_layer_entry(BENCH, m)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric_is_a_file(m):
    check_end_to_end_entry(BENCH, m)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_every_piece(w):
    check_cell(BENCH, w)


def test_no_reader_is_left_without_its_entry():
    check_every_reader_has_an_entry(BENCH)


# --- the tests of this directory take an append -------------------------------

LATER = "later-model.batch-decode"


def appended(bench):
    """``bench`` as a later ``model_config`` PR would leave it: one more
    configuration (an existing file under another name), its cell last in
    ``workloads``, in ``tokens_per_s`` and in every list the batch cells
    share, and one more per-layer entry at the end."""
    out = json.loads(json.dumps(bench))
    batch = [m for m in out["end_to_end"]
             if m["name"] == "tokens_per_s"][0]["workloads"]
    like = [w for w in out["workloads"] if w["name"] == batch[0]][0]
    cfg = [c for c in out["configs"] if c["name"] == like["config"]][0]
    out["configs"].append(dict(cfg, name="later-model"))
    out["workloads"].append(dict(like, name=LATER, config="later-model"))
    every = set(batch)
    for m in out["end_to_end"] + out["per_layer"]:
        if every <= set(m.get("workloads", ())):
            m["workloads"].append(LATER)
    out["per_layer"].append({
        "name": "device.idle_share.later", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "tokens_per_s",
        "workloads": [LATER]})
    return out


def moved_before(bench, name, before, rows=True, lists=True):
    """``bench`` with the cell ``name`` taken out and put back BEFORE the
    cell ``before``: in ``workloads`` (``rows``) and in every list that
    holds both (``lists``)."""
    out = json.loads(json.dumps(bench))

    def move(names, items):
        if name in names and before in names:
            item = items.pop(names.index(name))
            names.remove(name)
            items.insert(names.index(before), item)

    if rows:
        move([w["name"] for w in out["workloads"]], out["workloads"])
    for m in (out["end_to_end"] + out["per_layer"]) if lists else ():
        move(list(m.get("workloads", ())), m.get("workloads"))
    return out


def hold_to_the_contract(bench):
    """Every file's checks of the benchmark's entries, on ``bench``: each
    ``test_bm_*.py`` of this directory, a later PR's too, is held to the
    contract through its functions named ``check_*`` that take the
    benchmark alone; this file's checks of one entry run over them all."""
    import glob
    import importlib
    import inspect

    here = os.path.dirname(os.path.abspath(__file__))
    found = []
    for path in sorted(glob.glob(os.path.join(here, "test_bm_*.py"))):
        mod = importlib.import_module(os.path.basename(path)[:-3])
        for name, fn in sorted(vars(mod).items()):
            if name.startswith("check_") and inspect.isfunction(fn) \
                    and fn.__module__ == mod.__name__ \
                    and list(inspect.signature(fn).parameters) == ["bench"]:
                fn(bench)
                found.append(f"{mod.__name__}.{name}")
    assert {"test_bm_window_moe.check_cell_entries",
            "test_bm_thread_spans.check_the_twelve",
            "test_bm_harness.check_every_reader_has_an_entry"} <= set(found)
    for m in bench["per_layer"]:
        check_per_layer_entry(bench, m)
    for m in bench["end_to_end"]:
        check_end_to_end_entry(bench, m)
    for w in bench["workloads"]:
        check_cell(bench, w)


def _swap_two_of_the_seven(bench):
    out = json.loads(json.dumps(bench))
    names = [m["name"] for m in out["per_layer"]]
    i = names.index("programs.window_attn_share")
    out["per_layer"].insert(i - 2, out["per_layer"].pop(i))
    return out


def _later_entry_among_the_twelve(bench):
    out = json.loads(json.dumps(bench))
    names = [m["name"] for m in out["per_layer"]]
    out["per_layer"].insert(names.index("frontdoor.handoff_ms.chat"),
                            out["per_layer"].pop())
    return out


def _later_config_before_pr36s(bench):
    out = json.loads(json.dumps(bench))
    out["configs"].insert(4, out["configs"].pop())
    return out


FAULTS = {
    # the later cell put BEFORE PR 36's, in ``workloads`` and in the lists
    "cell-inserted-before-an-accepted-one": lambda b: moved_before(
        b, LATER, "command-a-plus-05-2026.doc-reasoning-decode"),
    # ... in ``workloads`` alone: the shared lists then disagree with it
    "cell-inserted-in-workloads-alone": lambda b: moved_before(
        b, LATER, "command-a-plus-05-2026.doc-reasoning-decode", lists=False),
    # ... in the shared lists alone
    "cell-inserted-in-the-lists-alone": lambda b: moved_before(
        b, LATER, "command-a-plus-05-2026.doc-reasoning-decode", rows=False),
    "one-of-pr36s-seven-moved": _swap_two_of_the_seven,
    "entry-inserted-among-pr39s-twelve": _later_entry_among_the_twelve,
    "configuration-inserted-before-an-accepted-one": _later_config_before_pr36s,
}


def test_the_tests_take_an_append():
    """Pure JSON: the structural checks of every file pass on the benchmark
    as it is and on a copy with a later PR's configuration, cell and entry
    appended; nothing that was there has moved in the copy."""
    hold_to_the_contract(BENCH)
    later = appended(BENCH)
    hold_to_the_contract(later)
    for group in ("configs", "workloads", "per_layer", "end_to_end"):
        old = [m["name"] for m in BENCH[group]]
        assert [m["name"] for m in later[group]][:len(old)] == old
    for was, now in zip(BENCH["end_to_end"] + BENCH["per_layer"],
                        later["end_to_end"] + later["per_layer"]):
        ws = was.get("workloads", [])
        assert now.get("workloads", [])[:len(ws)] == ws


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_the_appended_copy_fails(fault):
    broken = FAULTS[fault](appended(BENCH))
    with pytest.raises((AssertionError, ValueError)):
        hold_to_the_contract(broken)


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.Cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no.such.metric")
    # a group of cells is a suffix on the entry's name, not a file
    assert callable(harness.load_reader("device.idle_share.chat").read)
    assert not os.path.exists(os.path.join(
        harness.HERE, "layer_metrics", "device.idle_share.chat.py"))


@pytest.fixture(scope="module")
def tiny_model():
    from benchmarks.models import llama_dense

    return llama_dense.build(TINY, 3_000_000_019)


def test_reference_agrees_with_the_model_at_tiny_size(tiny_model):
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from benchmarks.models import llama_dense
    from benchmarks.reference import dense_decoder

    ids = np.random.default_rng(0).integers(1, 256, 48).tolist()
    with paddle.no_grad():
        got = tiny_model(Tensor(jnp.asarray([ids])))._value[0]
    w = llama_dense.reference_weights(tiny_model)
    res = dense_decoder.compare(got, dense_decoder.reference_logits(w, TINY, ids),
                                atol=0.05, rms_rel=0.05)
    assert res["ok"] and res["rms_rel"] < 0.02, res
    # and it is tight enough to tell a wrong model: drop a layer, or scale
    # the final norm
    wrong = dict(w, layers=w["layers"][:1])
    bad = dense_decoder.compare(got, dense_decoder.reference_logits(wrong, TINY, ids),
                                atol=0.05, rms_rel=0.05)
    assert not bad["ok"]
    bad = dense_decoder.compare(
        got, dense_decoder.reference_logits(dict(w, norm=w["norm"] * 1.25), TINY, ids),
        atol=0.05, rms_rel=0.05)
    assert not bad["ok"]


def test_seed_decides_the_weights(tiny_model):
    import numpy as np

    from benchmarks.models import llama_dense

    a = llama_dense.build(TINY, 3_000_000_019)
    b = llama_dense.build(TINY, 5)
    pick = lambda m: np.asarray(
        dict(m.named_parameters())["lm_head.weight"]._value, np.float32)
    assert (pick(a) == pick(tiny_model)).all() and (pick(a) != pick(b)).any()
    assert str(dict(a.named_parameters())["lm_head.weight"].dtype).endswith("bfloat16")


# --- the probe reads the program's step call by name, and says so when it cannot

def _step(order, vocab=256, n_out=5):
    """A stand-in for a jitted step program taking ``order``'s arguments."""
    import numpy as np

    src = "def fn(%s):\n    return out" % ", ".join(order)
    env = {"out": (None, np.zeros((4, vocab), np.float32), None, (), ())[:n_out]}
    exec(src, env)
    return env["fn"]


class _Engine:
    class kv:
        occupancy = staticmethod(lambda: 0.25)

    def _step_call(self, program, bucket, fn, *args):
        return fn(*args)


DECODE_ARGS = ("param_vals", "k_pools", "v_pools", "ids", "pos", "tables",
               "lens", "slot_blocks", "slot_offsets")


def _decode_values(lens_dtype="int32"):
    import numpy as np

    return {"ids": np.zeros((4, 1), np.int64), "tables": np.zeros((4, 8), np.int32),
            "lens": np.array([9, 1, 30, 1]).astype(lens_dtype)}


@pytest.mark.parametrize("order", [DECODE_ARGS, DECODE_ARGS[::-1]],
                         ids=["as-today", "reordered"])
@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_probe_counts_rows_by_argument_name(order, program):
    from benchmarks import launcher

    eng = _Engine()
    probe = launcher.Probe(eng, vocab=256)
    vals = _decode_values()
    eng._step_call(program, (4, 8), _step(order), *(vals.get(n) for n in order))
    assert probe.n[f"{program}_launches"] == 1
    assert probe.n[f"{program}_rows"] == 2          # two rows are padding
    assert probe.n[f"{program}_kv_tokens"] == 39
    assert probe.pool_peak == 0.25
    assert probe.rows_hist == ({2: 1} if program == "decode" else {})


@pytest.mark.parametrize("case", ["lens-renamed", "lens-float", "wrong-rows",
                                  "logits-vocab", "return-tuple"])
def test_probe_fails_loudly_when_the_step_call_changed(case):
    from benchmarks import launcher

    eng = _Engine()
    launcher.Probe(eng, vocab=256)
    order, vals, fn_kw, bucket = DECODE_ARGS, _decode_values(), {}, (4, 8)
    if case == "lens-renamed":
        order = tuple("kv_lens" if n == "lens" else n for n in order)
        vals["kv_lens"] = vals.pop("lens")
    elif case == "lens-float":
        vals = _decode_values("float32")
    elif case == "wrong-rows":
        bucket = (8, 8)
    elif case == "logits-vocab":
        fn_kw = {"vocab": 255}
    elif case == "return-tuple":
        fn_kw = {"n_out": 4}
    with pytest.raises(launcher.ProbeMismatch):
        eng._step_call("decode", bucket, _step(order, **fn_kw),
                       *(vals.get(n) for n in order))


def test_probe_counts_prompt_tokens_and_checks_the_position():
    import numpy as np
    from benchmarks import launcher

    eng = _Engine()
    probe = launcher.Probe(eng, vocab=256)
    order = ("param_vals", "k_pools", "v_pools", "ids", "last_pos", "blocks", "offs")
    vals = {"ids": np.zeros((1, 64), np.int64), "last_pos": np.int32(39)}
    eng._step_call("prefill", (64,), _step(order), *(vals.get(n) for n in order))
    assert (probe.n["prefill_tokens"], probe.n["prefill_tokens_sq"]) == (40, 1600)
    vals["last_pos"] = np.int32(64)
    with pytest.raises(launcher.ProbeMismatch):
        eng._step_call("prefill", (64,), _step(order), *(vals.get(n) for n in order))


def test_the_knee_in_the_traffic_file_is_the_sweeps():
    from benchmarks import sweep

    kept = harness.load_json(harness.HERE, "sweeps", "chat-steady_knee.json")
    mix = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    found = sweep.knee(kept["rows"])
    assert found == {k: mix[k] for k in ("knee_rps", "knee_criterion")}
    assert found == {k: kept["knee"][k] for k in ("knee_rps", "knee_criterion")}
    assert mix["rate_rps"] / mix["knee_rps"] == pytest.approx(
        mix["rate_share_of_knee"], abs=0.005)
    # every rate up to the knee ran on the plateau; a failure ends it
    rows = [dict(r, failed=int(r["rate_rps"] == 2.75)) for r in kept["rows"]]
    assert sweep.knee(rows)["knee_rps"] == 2.5
    with pytest.raises(ValueError):
        sweep.knee([dict(kept["rows"][0], compiles=1)])


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **kw)
    env.pop("XLA_FLAGS", None)
    return env


def test_client_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import benchmarks.run, "
            "benchmarks.sweep, benchmarks.traffic_kinds.open_loop, "
            "benchmarks.traffic_kinds.backlog, benchmarks.layer_lib; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'numpy', 'paddle_tpu')]; assert not bad, bad"
            % harness.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_command_line_exits_non_zero_off_tpu_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        env=_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs 1 tpu chip" in r.stderr


def test_a_later_pr_adds_a_cell_by_adding_files_only(tmp_path):
    """A dummy configuration, mix, metric and cell as NEW files in a copy;
    nothing that was there is edited (BENCHMARK.json gains entries)."""
    from benchmarks import run

    root = str(tmp_path)
    shutil.copytree(harness.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(os.path.join(root, "benchmarks"))
              for p in fs}
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny.json"), "w") as f:
        json.dump(TINY, f)
    mix = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    mix.update(rate_rps=6.0, lead_in_s=1, lead_out_s=1, trace_s=0.5,
               prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.5,
                           "min": 8, "max": 64},
               output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 4, "max": 16})
    with open(os.path.join(bdir, "traffic", "tiny-chat.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "layer_metrics", "dummy.prompt_tokens.py"), "w") as f:
        f.write('UNIT, LAYER, SOURCE = "tokens", "scheduler", '
                '"program_counter"\n\n\ndef read(counters, trace):\n'
                '    return counters["window"]["probe"]["prefill_tokens"]\n')
    bench = json.loads(json.dumps(BENCH))
    name = "tiny.tiny-chat"
    bench["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny.json", "why": "t"})
    bench["workloads"].append({"name": name, "config": "tiny", "chips": 1,
                               "traffic": "tiny-chat", "why": "t"})
    chat = BENCH["workloads"][0]["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if chat in m.get("workloads", ()):
            m["workloads"].append(name)
    bench["per_layer"].append({"name": "dummy.prompt_tokens", "unit": "tokens",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "tpot_p50_ms",
                               "workloads": [name]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    lines = {}
    for trace in (False, True):
        out = io.StringIO()
        rc = run.run_cell(name, 3_000_000_019, 4.0, trace, root=root,
                          platform="cpu", out=out, records_path=""
                          if trace else os.path.join(root, "rec", "r.json.gz"))
        assert rc == 0
        lines[trace] = json.loads(out.getvalue().strip().splitlines()[-1])
    e2e, layer = lines[False], lines[True]
    assert e2e["correct"] and e2e["failed"] == 0 and e2e["attempted"] == 24
    assert set(e2e["metrics"]) == {"tpot_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in e2e["metrics"].values())
    assert e2e["device"]["platform"] == "cpu"      # never a device metric
    # the window's records, written on request, give the same metrics back
    rec = records.load(records.read(os.path.join(root, "rec", "r.json.gz")))
    for m in ("tpot_p50_ms",):
        again = harness.load_module("e2e_metrics", m).compute(rec)
        assert again == pytest.approx(e2e["metrics"][m]["value"], abs=2e-3)
    # the tail of the gaps is per-layer since PR 42: in the traced line, as
    # the client prints it
    assert layer["metrics"]["frontdoor.itl_p995_ms"]["value"] == \
        layer["detail"]["client"]["shape"]["itl_p99.5_ms"] > 0
    # the TTFT tail is per-layer since PR 27: in the traced line, and among
    # what the client prints in both
    assert layer["metrics"]["frontdoor.ttft_p90_ms"]["value"] == \
        layer["detail"]["client"]["ttft_p90_ms"] > 0
    assert e2e["detail"]["client"]["ttft_p90_ms"] > 0
    assert layer["metrics"]["dummy.prompt_tokens"]["value"] > 0
    assert layer["metrics"]["programs.compiles_in_window.chat"]["value"] == 0
    assert 0 < layer["metrics"]["cache.pool_peak_share.chat"]["value"] <= 100
    assert layer["metrics"]["programs.warm_s_per_program"]["value"] > 0
    assert "device.idle_share.chat" not in layer["metrics"]    # no trace: left out
    assert layer["detail"]["check"]["ok"]
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(bdir) for p in fs if "__pycache__" not in dp}
    assert all(after[p] == before[p] for p in before)
    # the compilation cache sits at a fixed path inside the checkout
    assert run.child_env(root)["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(root, ".jax_compile_cache")
