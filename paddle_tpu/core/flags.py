"""Runtime flag registry.

TPU-native analog of the reference's gflags-style flag system
(``paddle/common/flags.cc`` — ~138 ``PD_DEFINE_*`` flags, readable/settable
from Python via ``paddle.set_flags``/``get_flags``).  Here flags are a plain
process-local registry, mirrored from ``FLAGS_*`` environment variables at
import time.  XLA-level knobs route through ``XLA_FLAGS`` instead; these
flags only control framework behavior (NaN checks, eager debug, etc.).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Mapping, Union

_DEFS: Dict[str, dict] = {}
_VALUES: Dict[str, Any] = {}


def define_flag(name: str, default: Any, help_str: str = "",
                on_set=None) -> None:
    """Register a flag with a default value. Env var FLAGS_<name> overrides.
    ``on_set(value)`` runs on every change — the hook that lets a flag
    steer live config (e.g. jax matmul precision)."""
    _DEFS[name] = {"default": default, "help": help_str,
                   "type": type(default), "on_set": on_set}
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        _VALUES[name] = _parse(env, type(default))
        if on_set is not None:
            on_set(_VALUES[name])
    else:
        _VALUES[name] = default


def _parse(text: str, ty: type) -> Any:
    if ty is bool:
        return text.lower() in ("1", "true", "yes", "on")
    if ty in (int, float):
        return ty(text)
    return text


def set_flags(flags: Mapping[str, Any]) -> None:
    """Set one or more registered flags (``paddle.set_flags`` analog)."""
    for name, value in flags.items():
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _DEFS:
            raise ValueError(f"Unknown flag: {name}")
        _VALUES[key] = _parse(value, _DEFS[key]["type"]) if isinstance(value, str) else value
        cb = _DEFS[key].get("on_set")
        if cb is not None:
            cb(_VALUES[key])


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    """Read registered flags (``paddle.get_flags`` analog)."""
    if flags is None:
        return dict(_VALUES)
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _DEFS:
            raise ValueError(f"Unknown flag: {name}")
        out[name] = _VALUES[key]
    return out


def flag(name: str) -> Any:
    """Fast internal accessor."""
    return _VALUES[name]


def _jax_config(key):
    def setter(value):
        import jax

        jax.config.update(key, value)

    return setter


def _env_mirror(env_key):
    """Mirror a flag into an env var (knobs XLA reads at backend init)."""

    def setter(value):
        os.environ[env_key] = str(value)

    return setter


# --- Core framework flags -------------------------------------------------
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf in eager mode.")
define_flag("check_nan_inf_level", 0, "0: error on NaN/Inf; 1: warn; 3: dump stats only.")
define_flag("eager_log_ops", False, "Log every eager op dispatch (debug).")
define_flag("use_donated_buffers", True, "Donate input buffers in jitted train steps.")
define_flag("default_dtype", "float32", "Default floating point dtype.")
define_flag("retain_grad_for_all", False, "Retain .grad for non-leaf tensors.")
define_flag("benchmark", False, "Block on every op for accurate eager timing.")
define_flag("call_stack_level", 1,
            "Error verbosity: 0 brief, 1 normal, 2 full tracebacks.")

# --- Numerics / precision (FLAGS_cudnn_deterministic family) ---------------
define_flag("matmul_precision", "default",
            "MXU matmul precision: default|high|highest "
            "(jax_default_matmul_precision).",
            on_set=_jax_config("jax_default_matmul_precision"))
define_flag("deterministic", False,
            "Bit-deterministic kernel selection "
            "(FLAGS_cudnn_deterministic/embedding_deterministic analog; "
            "maps to --xla_gpu_deterministic-class knobs; on TPU most ops "
            "are already deterministic).")
define_flag("low_precision_op_list", False,
            "Record which ops AMP ran in low precision "
            "(FLAGS_low_precision_op_list; read via "
            "paddle.amp.debugging.low_precision_op_list()).")
define_flag("debug_nans", False,
            "Trap NaNs inside jitted programs (jax_debug_nans).",
            on_set=_jax_config("jax_debug_nans"))

# --- Compiler / jit (CINN + executor flag family) ---------------------------
define_flag("log_compiles", False, "Log every XLA compilation (jax_log_compiles).",
            on_set=_jax_config("jax_log_compiles"))
define_flag("jit_cache_max_entries", 64,
            "Max compiled entries per to_static function before eviction.")
define_flag("jit_partial_graph", True,
            "After a to_static graph break, record the eager run as a "
            "linear trace, compile segments between host sync points, and "
            "replay them with value guards (SOT partial-graph analog).")
def _bool_env_mirror(env_key):
    """Mirror a boolean flag into the env var the kernel gates actually
    read ("1"/unset) so spawned workers inherit it."""

    def setter(value):
        if value:
            os.environ[env_key] = "1"
        else:
            os.environ.pop(env_key, None)

    return setter


define_flag("disable_pallas_kernels", False,
            "Force the XLA composite path for all Pallas kernels "
            "(mirrors to PADDLE_TPU_DISABLE_PALLAS for subprocesses).",
            on_set=_bool_env_mirror("PADDLE_TPU_DISABLE_PALLAS"))
define_flag("pallas_autotune", False,
            "Measured block-size sweep for Pallas flash attention, memoized "
            "per shape/dtype/device (the N11 autotune-cache analog).")

# --- Memory (allocator facade family: FLAGS_fraction_of_gpu_memory...) -----
define_flag("memory_fraction", 0.75,
            "Fraction of device HBM XLA may preallocate "
            "(XLA_PYTHON_CLIENT_MEM_FRACTION; applies to backends "
            "initialized after the change).",
            on_set=_env_mirror("XLA_PYTHON_CLIENT_MEM_FRACTION"))
define_flag("preallocate_memory", True,
            "Preallocate the HBM pool at backend init "
            "(XLA_PYTHON_CLIENT_PREALLOCATE).",
            on_set=lambda v: os.environ.__setitem__(
                "XLA_PYTHON_CLIENT_PREALLOCATE", "true" if v else "false"))
define_flag("init_allocated_mem", False,
            "Fill fresh allocations with a debug pattern "
            "(FLAGS_init_allocated_mem; debug aid, CPU-path only).")

# --- Distributed (NCCL/watchdog flag family) --------------------------------
define_flag("tcp_store_timeout", 30.0,
            "Rendezvous store connect timeout in seconds (FLAGS_*_timeout).")
define_flag("watchdog_timeout", 600.0,
            "Step watchdog timeout in seconds "
            "(comm_task_manager hang detection analog).")
define_flag("sync_collectives", False,
            "Block after each eager collective "
            "(FLAGS_sync_nccl_allreduce analog; debugging).")

# --- Data loading (io flag family) ------------------------------------------
define_flag("dataloader_use_shared_memory", True,
            "Use the native shm-ring for multi-worker DataLoader batches.")
define_flag("dataloader_shm_slots", 8,
            "Slots in the shared-memory ring per DataLoader.")
define_flag("dataloader_prefetch", 2,
            "Prefetch factor per DataLoader worker.")

# --- Profiler ---------------------------------------------------------------
define_flag("enable_profiler", False,
            "Arm the profiler at startup (FLAGS_enable_record_op_info-ish).")
define_flag("host_trace_level", 1,
            "Profiler host instrumentation verbosity (FLAGS_host_trace_level).")
