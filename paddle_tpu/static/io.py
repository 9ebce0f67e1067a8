"""Static-graph save/load (``python/paddle/static/io.py`` capability).

TPU-first: the portable serialized form of a Program is its jitted replay
exported as StableHLO (``jax.export``) — parameters freeze into the
artifact as constants, exactly what an inference export wants — plus a
pickled name→array map for the trainable state (the pdmodel/pdiparams
pair).  ``load_inference_model`` returns a loaded-program object the
``Executor`` runs directly.
"""

from __future__ import annotations

import os
import pickle
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax import export as jax_export

from ..core.tensor import Parameter, Tensor
                                               # binding (ISSUE 15)

_MODEL_SUFFIX = ".pdmodel"
_PARAMS_SUFFIX = ".pdiparams"


def _program():
    from . import default_main_program

    return default_main_program()


def _named_params(program) -> Dict[str, Parameter]:
    out: Dict[str, Parameter] = {}
    seen = set()
    i = 0
    for t in program._keepalive:
        if isinstance(t, Parameter) and id(t) not in seen:
            seen.add(id(t))
            out[t.name or f"param_{i}"] = t
            i += 1
    return out


# --- program state (``load_program_state``/``set_program_state``) ----------

def save(program, path: str, protocol: int = 4):
    """(``static/io.py`` save) persist every parameter of ``program``."""
    state = {k: p._host_read() for k, p in _named_params(program).items()}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + _PARAMS_SUFFIX if not path.endswith(_PARAMS_SUFFIX)
              else path, "wb") as f:
        pickle.dump(state, f, protocol=protocol)


def load(program, path: str, executor=None, var_list=None):
    """(``static/io.py`` load) restore parameters saved by :func:`save`."""
    p = path if path.endswith(_PARAMS_SUFFIX) else path + _PARAMS_SUFFIX
    with open(p, "rb") as f:
        state = pickle.load(f)
    set_program_state(program, state)


def load_program_state(model_path: str, var_list=None) -> Dict[str, Any]:
    p = (model_path if model_path.endswith(_PARAMS_SUFFIX)
         else model_path + _PARAMS_SUFFIX)
    with open(p, "rb") as f:
        return pickle.load(f)


def set_program_state(program, state_dict: Dict[str, Any]):
    params = _named_params(program)
    for k, v in state_dict.items():
        if k in params:
            # set_value: copy-on-ingest + loud shape check + dtype keep
            try:
                params[k].set_value(v)
            except ValueError as e:
                raise ValueError(f"set_program_state: {k}: {e}") from None


# --- inference export (``save_inference_model`` family) --------------------

class _LoadedProgram:
    """Deserialized inference program: a StableHLO artifact + feed/fetch
    naming.  ``Executor.run`` executes it directly."""

    def __init__(self, exported, feed_names: List[str],
                 fetch_names: List[str]):
        self._exported = exported
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)

    def run_feed(self, feed: Dict[str, Any]):
        import jax.numpy as jnp

        args = [jnp.asarray(feed[n]) for n in self.feed_names]
        out = self._exported.call(*args)
        return list(out) if isinstance(out, (list, tuple)) else [out]


def normalize_program(program, feed_vars, fetch_vars, **kwargs):
    """(``static/io.py`` normalize_program) prune the program to the nodes
    the fetch targets actually depend on (dead-op elimination)."""
    from . import Program

    fetch_ids = {id(v) for v in fetch_vars}
    keep = [False] * len(program.nodes)
    needed = set(fetch_ids)
    for i in range(len(program.nodes) - 1, -1, -1):
        node = program.nodes[i]
        outs = [o for o in node.out_ids if o is not None]
        if node.kind == "alias":
            if node.out_ids[0] in needed:
                keep[i] = True
                needed.add(node.src_id)
            continue
        if any(o in needed for o in outs):
            keep[i] = True
            needed.update(a for a in node.arg_ids if a is not None)
    pruned = Program()
    pruned.nodes = [n for n, k in zip(program.nodes, keep) if k]
    pruned.placeholders = dict(program.placeholders)
    pruned._keepalive = list(program._keepalive)
    pruned.state_ids = list(program.state_ids)
    return pruned


def _export_bytes(program, feed_vars, fetch_vars) -> bytes:
    feed_ids = [id(v) for v in feed_vars]
    fetch_ids = [id(v) for v in fetch_vars]
    nodes = list(program.nodes)

    def pure(*feed_vals):
        from . import _replay_nodes

        env = dict(zip(feed_ids, feed_vals))
        env = _replay_nodes(nodes, env)
        return tuple(env.get(fid, v._value)
                     for fid, v in zip(fetch_ids, fetch_vars))

    specs = [jax.ShapeDtypeStruct(tuple(v.shape), v._value.dtype)
             for v in feed_vars]
    exported = jax_export.export(jax.jit(pure))(*specs)
    return exported.serialize()


def serialize_program(feed_vars, fetch_vars, program=None, **kwargs) -> bytes:
    program = program or _program()
    return _export_bytes(program, _as_list(feed_vars), _as_list(fetch_vars))


def deserialize_program(data: bytes):
    exported = jax_export.deserialize(data)
    n_in = len(exported.in_avals)
    return _LoadedProgram(exported, [f"feed_{i}" for i in range(n_in)],
                          [f"fetch_{i}" for i in range(len(exported.out_avals))])


def serialize_persistables(feed_vars, fetch_vars, executor=None,
                           program=None, **kwargs) -> bytes:
    program = program or _program()
    state = {k: p._host_read() for k, p in _named_params(program).items()}
    return pickle.dumps(state)


def deserialize_persistables(program, data: bytes, executor=None):
    set_program_state(program, pickle.loads(data))


def save_to_file(path: str, content: bytes):
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _as_list(v):
    return list(v) if isinstance(v, (list, tuple)) else [v]


def _feed_name(program, var) -> str:
    for name, tid in program.placeholders.items():
        if tid == id(var):
            return name
    return var.name or f"feed_{id(var)}"


def save_inference_model(path_prefix: str, feed_vars, fetch_vars, executor=None,
                         program=None, **kwargs):
    """(``static/io.py`` save_inference_model) export the fetch
    computation over the feed placeholders as StableHLO + metadata."""
    program = program or _program()
    feed_vars = _as_list(feed_vars)
    fetch_vars = _as_list(fetch_vars)
    blob = _export_bytes(program, feed_vars, fetch_vars)
    meta = {
        "feed_names": [_feed_name(program, v) for v in feed_vars],
        "fetch_names": [v.name or f"fetch_{i}"
                        for i, v in enumerate(fetch_vars)],
    }
    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    save_to_file(path_prefix + _MODEL_SUFFIX,
                 pickle.dumps({"stablehlo": blob, "meta": meta}))
    # params are frozen into the artifact; pdiparams records the state for
    # train-resume parity
    save_to_file(path_prefix + _PARAMS_SUFFIX,
                 serialize_persistables(feed_vars, fetch_vars,
                                        program=program))


def load_inference_model(path_prefix: str, executor=None, **kwargs):
    """Returns ``[loaded_program, feed_names, fetch_names]`` — run it with
    ``Executor.run(program=loaded_program, feed=..., fetch_list=...)``."""
    raw = pickle.loads(load_from_file(path_prefix + _MODEL_SUFFIX))
    exported = jax_export.deserialize(raw["stablehlo"])
    lp = _LoadedProgram(exported, raw["meta"]["feed_names"],
                        raw["meta"]["fetch_names"])
    return [lp, lp.feed_names, lp.fetch_names]
