"""Chip bring-up (ISSUE 21): what keeps the serving path startable on a TPU.

The contract under test: importing the package and its launchers
initialises no JAX backend (one process per chip: a parent that touched
JAX holds the device its children need); ``chip_smoke.py``'s body — the
same code the chip runs at Llama-3-8B widths — serves its waves and passes
its own assertions at a tiny size on CPU with the kernels in interpret
mode, while the script's entry refuses anything but a TPU; a kernel that
raises is never retried on the XLA path; the compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says or at one fixed path in the checkout;
and shapes the compiled ragged kernel cannot take are refused at build or
at admission with an error that names the limit.

(Named ``zzzzzzzzzzz`` — 11 z's — to sort after
``test_zzzzzzzzzz_disagg.py``: the tier-1 suite overruns its timeout, so
new dots must only append.)
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.audit import AuditConfig
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    SamplingParams,
    SchedulerConfig,
)
from paddle_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402  (the script at the repo root)

_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
            PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))


def _engine(**kw):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    return EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=4, **kw))


# --- one process per chip ---------------------------------------------------

def test_importing_package_and_launchers_initialises_no_backend():
    code = (
        "import importlib\n"
        "for m in ('paddle_tpu', 'paddle_tpu.serving.server',\n"
        "          'paddle_tpu.serving.procfleet',\n"
        "          'paddle_tpu.serving.worker',\n"
        "          'paddle_tpu.distributed.launch.main'):\n"
        "    importlib.import_module(m)\n"
        "from jax._src import xla_bridge\n"
        "print('BACKENDS', sorted(xla_bridge._backends))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=_ENV,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BACKENDS []" in proc.stdout, proc.stdout


def test_worker_refuses_to_share_a_tpu_host(monkeypatch):
    """One of several workers on a TPU host would take every chip: it
    refuses with a message that says why (nothing pins workers yet)."""
    from paddle_tpu.serving import worker

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "configure_compile_cache",
                        lambda: "/nonexistent")
    with pytest.raises(SystemExit) as exc:
        worker.main(["--replica", "0", "--fleet-size", "2"])
    assert "one of 2 workers" in str(exc.value)
    assert "pinned" in str(exc.value)


# --- the smoke: body on CPU, entry demands the chip -------------------------

@pytest.fixture
def _restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_smoke_body_serves_tiny_model_in_interpret_mode(
        _restore_cache_config):
    sizes = chip_smoke.Sizes(
        model=dict(LlamaConfig.tiny(num_hidden_layers=1).__dict__),
        num_blocks=128, block_size=4, prompt_lens=(20, 8), requests=6,
        max_tokens=4, token_budget=16, burst_steps=4, audit_every=4,
        logit_atol=1e-3, logit_rtol=1e-3,   # the toy's logits are tiny
        use_pallas=True)
    result = chip_smoke.run_smoke(sizes)
    assert result["depth"] == 1
    for name, leg in result["legs"].items():
        assert [w["label"] for w in leg["waves"]] == \
            ["cold", "cached", "repeat"]
        assert leg["waves"][0]["traced"], name      # cold compiled
        assert not leg["waves"][2]["traced"], name  # repeat did not
        assert leg["audited"] > 0, name
    assert result["legs"]["default"]["paths"] == {"decode": "pallas"}
    assert result["legs"]["unified+burst"]["paths"] == {
        "ragged": "pallas", "burst": "pallas"}


def test_smoke_entry_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=_ENV,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout      # no result line


# --- no fallback: a kernel failure raises -----------------------------------

class _Boom(RuntimeError):
    pass


def test_raising_kernel_propagates_out_of_pallas_dispatch():
    from paddle_tpu.ops.paged_attention import pallas_dispatch

    def kernel():
        raise _Boom("mosaic said no")

    with pytest.raises(_Boom):
        pallas_dispatch(kernel, lambda: "oracle", True, True)
    # the selection itself still works from what the code can see
    assert pallas_dispatch(kernel, lambda: "oracle", False, True) == \
        ("oracle", "xla")


def test_raising_kernel_propagates_out_of_flash_attention(monkeypatch):
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import pallas_flash

    def kernel(*a, **k):
        raise _Boom("mosaic said no")

    monkeypatch.setattr(fa, "use_flash", lambda shape, mask: True)
    monkeypatch.setattr(pallas_flash, "flash_attention", kernel)
    q = np.zeros((1, 8, 2, 16), np.float32)
    with pytest.raises(_Boom):
        fa.flash_attention_fwd(q, q, q, causal=True)


def test_strict_switches_are_gone():
    from paddle_tpu.core import flags

    with pytest.raises(Exception):
        flags.flag("strict_pallas")


# --- compile cache: one helper, one place -----------------------------------

def test_compile_cache_env_wins_and_code_sets_nothing(
        monkeypatch, tmp_path, _restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    # JAX read the variable itself when it was imported; code set nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(
        monkeypatch, _restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_compile_cache")
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.configure_compile_cache() == want  # never moves


# --- shapes the compiled kernels cannot take --------------------------------

def test_scalar_prefetch_over_smem_is_refused_by_name(monkeypatch):
    from paddle_tpu.ops import pallas_paged

    from paddle_tpu.ops.ragged_paged import max_table_width

    tables = np.zeros((2048, 128), np.int32)
    lens = np.zeros((2048,), np.int32)
    pallas_paged.check_scalar_prefetch("k", tables, lens)  # interpret: ok
    monkeypatch.setattr(pallas_paged, "_interpret", lambda: False)
    # the chip refused both: SMEM pads a table row to 128 words
    for refused in (tables, tables[:, :64]):
        with pytest.raises(ValueError, match="scalar memory"):
            pallas_paged.check_scalar_prefetch("k", refused, lens)
    pallas_paged.check_scalar_prefetch("k", tables[:1024], lens[:1024])
    assert max_table_width(2048) == 0
    assert max_table_width(1024) == 128
    assert max_table_width(256) == 512


def test_unified_engine_on_tpu_caps_context_at_admission():
    eng = _engine(unified_step=True,
                  scheduler=SchedulerConfig(max_tokens_per_step=16))
    assert eng.scheduler.seq_len_cap is None    # CPU: no scalar memory
    eng._cap_ragged_context()                   # what a TPU build does
    cap = eng.scheduler.seq_len_cap
    assert cap is not None and cap % eng.block_size == 0
    ok = eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=2))
    big = eng.add_request([1] * 8, SamplingParams(max_new_tokens=cap))
    eng.run(max_steps=50)
    assert ok.finish_reason.value == "length"
    assert big.finish_reason.value == "abort"
    assert "scalar memory" in big.error and str(cap) in big.error


def test_unified_engine_on_tpu_needs_a_token_budget():
    eng = _engine(unified_step=True)
    with pytest.raises(ValueError, match="max_tokens_per_step"):
        eng._cap_ragged_context()


# --- shadow oracle at a bf16 tolerance --------------------------------------

def test_argmax_flip_inside_the_tolerance_is_a_tie_not_a_divergence():
    ref = np.array([[1.00, 1.05, 0.0]], np.float32)
    primary = np.array([[1.05, 1.00, 0.0]], np.float32)

    def verdict(atol):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=1,
                                        logit_atol=atol, logit_rtol=0.0))
        eng.audit._reference_decode = lambda pools, inputs: ref
        return eng.audit._shadow_step(
            "decode", (), {}, primary, (1, 1),
            [{"id": "r", "greedy": True}])

    assert verdict(0.1) is None         # 0.05 apart: indistinguishable
    assert verdict(1e-4) == "token"     # float32 tolerance: a real flip
