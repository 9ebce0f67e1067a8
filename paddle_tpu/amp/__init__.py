from .auto_cast import amp_guard, auto_cast, decorate, white_list, black_list  # noqa: F401
from .grad_scaler import GradScaler  # noqa: F401
from . import debugging  # noqa: F401


def _dtype_supported(dtype) -> bool:
    """Probe the ACTIVE backend with a tiny computation — name lists would
    misreport PJRT plugin platforms, which register under names of their
    own."""
    import jax
    import jax.numpy as jnp

    try:
        (jnp.zeros((), dtype) + jnp.zeros((), dtype)).block_until_ready()
        return True
    except Exception:
        return False


def is_bfloat16_supported(device=None):
    """(``amp/__init__.py`` is_bfloat16_supported) — bf16 is the native
    matmul dtype on TPU; probed live on whatever backend is active."""
    import jax.numpy as jnp

    return _dtype_supported(jnp.bfloat16)


def is_float16_supported(device=None):
    """(``amp/__init__.py`` is_float16_supported) — probed live (fp16
    works on GPU/CPU; TPU accepts fp16 arrays, matmul is bf16-first)."""
    import jax.numpy as jnp

    return _dtype_supported(jnp.float16)
