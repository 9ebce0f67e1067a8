"""Trace Pallas regions with x64 disabled.

``paddle_tpu`` enables ``jax_enable_x64`` globally for reference dtype
parity (int64-default integer tensors).  Inside a Mosaic kernel that is a
liability: Python int constants in kernel bodies and BlockSpec index maps
trace as i64, and Mosaic has no i64 support — its int64→int32 conversion
helper recurses forever (jax 0.9 ``_convert_helper``).  Every
``pl.pallas_call`` site therefore traces its kernel and index maps under
this context, which pins the trace-time default back to 32-bit without
touching the global config.
"""

from __future__ import annotations

import jax


def no_x64():
    return jax.enable_x64(False)
