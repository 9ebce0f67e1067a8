"""``paddle.jit`` namespace: to_static + save/load of compiled graphs.

``jit.save`` exports the traced forward as serialized StableHLO
(``jax.export``) plus a pickled state dict — the analog of
``paddle.jit.save``'s pdmodel/pdiparams pair (``python/paddle/jit/api.py``,
C++ loader ``paddle/fluid/jit/``); ``jit.load`` returns a ``TranslatedLayer``
running the compiled artifact.
"""

from __future__ import annotations

import os
import pickle
from typing import Any, List, Optional

import jax
import numpy as np
from jax import export as jax_export

from ..core.tensor import Tensor
                                               # binding (ISSUE 15)
from .api import StaticFunction, in_to_static_trace, not_to_static, to_static  # noqa: F401


class TranslatedLayer:
    """Inference wrapper over a deserialized StableHLO artifact."""

    def __init__(self, exported, state_vals):
        self._exported = exported
        self._state_vals = state_vals

    def __call__(self, *args):
        raw = [a._value if isinstance(a, Tensor) else a for a in args]
        out = self._exported.call(self._state_vals, *raw)
        if isinstance(out, (list, tuple)):
            return type(out)(Tensor(o) for o in out)
        return Tensor(out)

    def forward(self, *args):
        return self(*args)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only")


def save(layer, path: str, input_spec=None, **configs):
    """Export ``layer.forward`` (or a function) to <path>.stablehlo + <path>.pdiparams."""
    from ..nn.layers import Layer
    from ..static import InputSpec

    if input_spec is None:
        raise ValueError("jit.save requires input_spec on the TPU runtime")

    examples = []
    for spec in input_spec:
        if isinstance(spec, InputSpec):
            shape = [1 if (s is None or s < 0) else s for s in spec.shape]
            examples.append(jax.ShapeDtypeStruct(tuple(shape), spec.dtype))
        elif isinstance(spec, Tensor):
            examples.append(jax.ShapeDtypeStruct(tuple(spec.shape), spec.dtype))
        else:
            raise TypeError(f"unsupported input spec: {spec}")

    if isinstance(layer, Layer):
        layer.eval()
        state = layer.state_dict()
        names = list(state.keys())
        vals = [state[n]._value for n in names]

        def fwd(state_vals, *xs):
            originals = [(state[n], state[n]._value) for n in names]
            for (t, _), v in zip(originals, state_vals):
                t._value = v
            try:
                wrapped = [Tensor(x) for x in xs]
                fn = layer.forward
                if isinstance(fn, StaticFunction):
                    fn = fn._fn
                out = fn(*wrapped)
            finally:
                for t, v in originals:
                    t._value = v
            if isinstance(out, (list, tuple)):
                return tuple(o._value for o in out)
            return out._value

        exported = jax_export.export(jax.jit(fwd))(
            [jax.ShapeDtypeStruct(np.shape(v), v.dtype) for v in vals], *examples
        )
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path + ".stablehlo", "wb") as f:
            f.write(exported.serialize())
        with open(path + ".pdiparams", "wb") as f:
            pickle.dump([np.asarray(v) for v in vals], f)
    else:
        raise TypeError("jit.save expects a Layer")


def load(path: str, **configs) -> TranslatedLayer:
    with open(path + ".stablehlo", "rb") as f:
        exported = jax_export.deserialize(f.read())
    with open(path + ".pdiparams", "rb") as f:
        vals = [jax.numpy.asarray(v) for v in pickle.load(f)]
    return TranslatedLayer(exported, vals)


def enable_to_static(flag: bool = True):
    global _enabled
    _enabled = flag


# --- SOT-style debugging knobs (``jit/sot/utils/envs.py`` capability) ------
_ignored_modules: set = set()


def ignore_module(modules) -> None:
    """(``jit/sot`` ignore_module) functions defined in these modules are
    never traced by ``to_static`` — they always run eagerly (the analog of
    SOT skipping frames from registered modules)."""
    if not isinstance(modules, (list, tuple, set)):
        modules = [modules]
    for m in modules:
        _ignored_modules.add(m.__name__ if hasattr(m, "__name__") else str(m))


def set_verbosity(level: int = 0, also_to_stderr: bool = False) -> None:
    """(``jit/sot`` set_verbosity) 0 = quiet; >0 logs each eager op
    dispatch (wired to the ``eager_log_ops`` flag)."""
    from ..core import flags

    flags.set_flags({"eager_log_ops": bool(level)})


def set_code_level(level: int = 0, also_to_stderr: bool = False) -> None:
    """(``jit/sot`` set_code_level) code-dump verbosity; on this substrate
    the compiled artifact is HLO — inspect it directly with
    ``StaticFunction.lowered_text`` (pointed to here for discoverability)."""
    # no bytecode rewriting exists to dump; the knob is accepted and the
    # HLO inspection path is the honest equivalent
    return None
