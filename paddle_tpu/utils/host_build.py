"""Build a model on the host CPU backend, then bulk-ship it to the device.

Eager parameter init dispatches one small XLA program per tensor (random
normal, zeros, PRNG key splits) and leaves every tensor on the default
device.  ``host_build(fn)`` runs ``fn`` with the host CPU as the default
JAX device — all eager init programs execute and stay in host memory —
then moves every parameter and buffer of the built Layer(s) to the
accelerator in ONE batched ``jax.device_put`` call (a pure data transfer,
zero device compiles).  Under an active mesh each tensor goes straight to
its shards, so a model that only fits sharded (Llama-3-8B in bf16 is 16 GB,
one v5e chip holds 16 GB) never has to exist whole on one device.
"""

from __future__ import annotations

from typing import Any, Callable


def host_build(build_fn: Callable[[], Any], log=None) -> Any:
    """Run ``build_fn`` on the host CPU backend; bulk-move results to device.

    ``build_fn`` is a zero-arg callable; every :class:`paddle_tpu.nn.Layer`
    and bare :class:`Tensor` found anywhere in its return value (walked
    through nested tuples/lists/dicts) has its parameters/buffers/value
    transferred.  Returns the ``build_fn`` output unchanged (Tensors are
    rebound in place).

    Falls back to a plain ``build_fn()`` call when no host CPU backend
    exists.
    """
    import jax

    from ..core.tensor import Tensor
    from ..nn import Layer

    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        if log:
            log("host_build: no host cpu backend; building on device")
        return build_fn()

    with jax.default_device(cpu):
        out = build_fn()

    # generic container walk: a Layer nested inside a dict (e.g.
    # {"model": m, "opt": o}) must not silently keep its parameters on
    # the host CPU
    layers, bare = [], []
    seen = set()

    def _walk(obj):
        if id(obj) in seen:
            return
        seen.add(id(obj))
        if isinstance(obj, Layer):
            layers.append(obj)
        elif isinstance(obj, Tensor):
            bare.append(obj)
        elif isinstance(obj, dict):
            for v in obj.values():
                _walk(v)
        elif isinstance(obj, (tuple, list)):
            for v in obj:
                _walk(v)

    _walk(out)

    from ..distributed import topology
    from ..parallel.utils import param_spec

    tensors = []
    for layer in layers:
        tensors.extend(layer.parameters())
        tensors.extend(layer.buffers())
    param_ids = {id(t) for t in tensors}
    tensors.extend(t for t in bare if id(t) not in param_ids)
    if not tensors:
        import warnings

        warnings.warn(
            "host_build: no Layers or Tensors found in build_fn's return "
            "value — nothing was transferred to the device (did the model "
            "end up inside an unsupported container?)", RuntimeWarning,
            stacklevel=2)

    from jax.sharding import NamedSharding

    mesh = topology.get_mesh()
    if mesh is not None and tensors:
        # active device mesh: place every tensor by its PartitionSpec
        # annotation (replicated default) — host init then shard-to-mesh,
        # the multi-chip init story (single-device placement would commit
        # tensors to one device and conflict with GSPMD constraints).
        # Still ONE batched device_put.
        if log:
            log(f"host_build: built on cpu ({len(tensors)} tensors); "
                f"sharding onto mesh "
                f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
        shardings = [NamedSharding(mesh, param_spec(t)) for t in tensors]
        values = jax.device_put([t._value for t in tensors], shardings)
        for t, v in zip(tensors, values):
            t._value = v
        return out
    if tensors:
        dev = jax.devices()[0]
        if log:
            log(f"host_build: built on cpu ({len(tensors)} tensors); "
                f"transferring to {dev.device_kind}")
        values = jax.device_put([t._value for t in tensors], dev)
        for t, v in zip(tensors, values):
            t._value = v
    return out
