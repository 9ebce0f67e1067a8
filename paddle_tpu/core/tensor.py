"""The ``Tensor`` facade over ``jax.Array``.

Capability analog of the reference's ``phi::DenseTensor``
(``paddle/phi/core/dense_tensor.h:37``) + eager ``AutogradMeta``
(``paddle/fluid/eager/autograd_meta.h:61``) + the Python Tensor method surface
(``python/paddle/tensor/*.py``, monkey-patched in ``base/dygraph/math_op_patch``).

Design notes (TPU-first):
  * ``_value`` is always a ``jax.Array`` (or a JAX tracer inside a
    ``to_static`` trace) — ops hand straight to XLA, no host round-trips.
  * The wrapper is mutable (supports paddle's in-place API surface:
    ``add_``, ``set_value``, ``__setitem__``, optimizer updates) while the
    underlying array is immutable; in-place ops rebind ``_value`` —
    functionalization in the sense of SURVEY.md §7 hard-part (c).
  * Autograd metadata lives on the wrapper: ``stop_gradient`` (paddle
    default True), ``grad``, and the producing ``GradNode`` slot.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from .autograd import run_backward


# Monotonic Tensor creation counter: partial-graph trace recording
# (jit/partial.py) uses it to detect tensors created DURING a recorded run
# outside op dispatch (host-computed values, to_tensor literals) — a linear
# replay cannot reproduce those, so the trace must be rejected.
_n_created = 0


class Tensor:
    __slots__ = (
        "_value",
        "stop_gradient",
        "grad",
        "_grad_node",
        "_out_index",
        "name",
        "persistable",
        "_backward_hooks",
        "_hook_counter",
        "trainable",
        "dist_attr",
        "dist_spec",
        "_ctr",
        "_view_base",
        "_view_index",
        "__weakref__",
    )

    def __init__(self, value, stop_gradient: bool = True, name: Optional[str] = None):
        if isinstance(value, Tensor):
            if value._value is None:
                # sparse tensors carry no dense payload (paddle.sparse);
                # re-wrapping one must not silently produce a broken Tensor
                raise RuntimeError(
                    f"{type(value).__name__} holds no dense buffer; call "
                    ".to_dense() before converting to a dense Tensor")
            value = value._value
        elif not isinstance(value, (jax.Array, jax.core.Tracer)):
            # jnp.array (copy) not jnp.asarray: jax's CPU backend zero-copy
            # aliases contiguous numpy buffers, but paddle ingestion
            # semantics are copy — a caller mutating its buffer (or torch
            # updating a shared-storage param in place) must not mutate us
            value = jnp.array(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._out_index = 0
        self.name = name
        self.persistable = False
        self._backward_hooks = None
        self._hook_counter = 0
        self.trainable = True
        self._view_base = None
        self._view_index = None
        global _n_created
        self._ctr = _n_created = _n_created + 1

    # --- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def ndim(self):
        return self._value.ndim

    # paddle alias
    @property
    def dim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def T(self):
        from .. import tensor as ops

        return ops.transpose(self, list(range(self.ndim))[::-1])

    @property
    def place(self):
        try:
            dev = list(self._value.devices())[0]
            return str(dev)
        except Exception:
            return "traced"

    @property
    def is_leaf(self):
        return self._grad_node is None

    def numel(self):
        return self.size

    # --- conversion ---------------------------------------------------------
    def numpy(self):
        """Full value as numpy.

        On a multi-process mesh, a value sharded across hosts is gathered
        with ``multihost_utils.process_allgather`` — a COLLECTIVE: every
        process must reach this call in lockstep (the SPMD contract; the
        reference's dist-tensor fetch gathers cross-rank the same way).
        Calling it rank-conditionally (``if rank == 0: t.numpy()``) will
        hang the job.  ``item``/``tolist``/``float()``/``print`` route
        through here and share the contract.
        """
        out = self._to_np()
        from .dispatch import notify_sync

        notify_sync(self, "numpy")
        return out

    def _host_read(self):
        """Read the full value onto the host for host-side computation
        (dynamic-shape ops like nonzero/masked_select, shape-from-tensor
        reads, observer statistics).  Reports the escape to an active
        partial-graph trace recorder — the host result can steer later
        Python invisibly, so a recorded trace that contains one cannot be
        replayed soundly."""
        from .dispatch import notify_sync

        notify_sync(self, "numpy")
        return self._to_np()

    def _to_np(self):
        """numpy() without the host-sync notification (internal paths and
        the scalar dunders, which report their own finer-grained sync
        kind so partial-graph recording can guard the value)."""
        v = self._value
        if (isinstance(v, jax.Array) and not v.is_fully_addressable
                and not v.is_fully_replicated):
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(v, tiled=True))
        return np.asarray(v)

    def _sync_scalar(self, kind: str):
        """Concretize to a host scalar, reporting (kind, value) to an
        active partial-graph trace recorder as a guardable sync point."""
        a = self._to_np()
        value = (bool(a) if kind == "bool" else int(a) if kind == "int"
                 else float(a) if kind == "float" else a.item())
        from .dispatch import notify_sync

        notify_sync(self, kind, value)
        return value

    def item(self, *args):
        if args:
            return self._value[args].item() if len(args) > 1 else self.numpy().flat[args[0]].item()
        return self._sync_scalar("item")

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def astype(self, dtype):
        from .dispatch import run_op

        d = dtype_mod.convert_dtype(dtype)
        return run_op("cast", lambda x: x.astype(d), self)

    cast = astype

    def to(self, *args, **kwargs):
        """paddle Tensor.to — dtype and/or device moves (device is a no-op on
        a single-process TPU runtime; sharding moves go through
        paddle_tpu.distributed.shard_tensor)."""
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a in ("cpu", "gpu", "tpu", "xpu") or str(a).startswith(("cpu", "gpu", "tpu")):
                continue
            try:
                d = dtype_mod.convert_dtype(a)
                out = out.astype(d)
            except Exception:
                continue
        return out

    def cpu(self):
        return Tensor(jax.device_get(self._value), stop_gradient=self.stop_gradient)

    def cuda(self, *a, **k):
        return self

    def pin_memory(self):
        return self

    # --- autograd surface ---------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def zero_grad(self):
        self.grad = None

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self._out_index = 0
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .dispatch import run_op

        return run_op("clone", lambda x: x + 0, self)

    def register_hook(self, hook):
        """Register a grad hook; returns a removable handle (eager/hooks.h)."""
        if self._backward_hooks is None:
            self._backward_hooks = {}
        hid = self._hook_counter
        self._hook_counter += 1
        self._backward_hooks[hid] = hook

        class _Handle:
            def __init__(self, t, hid):
                self._t, self._hid = t, hid

            def remove(self):
                self._t._backward_hooks.pop(self._hid, None)

        return _Handle(self, hid)

    # --- in-place machinery --------------------------------------------------
    def _rebind(self, other: "Tensor"):
        """Adopt another tensor's value + autograd slot (in-place op result)."""
        self._value = other._value
        self._grad_node = other._grad_node
        self._out_index = other._out_index
        self.stop_gradient = other.stop_gradient
        from .dispatch import notify_rebind

        notify_rebind(self, other)
        self._write_back_if_view()
        return self

    def _write_back_if_view(self):
        """Shared-storage view semantics, write direction (the reference's
        zero-copy stride views, ``paddle/phi/kernels/stride/``): an
        in-place mutation of a basic-index view writes through to its
        base tensor (``a = x[0]; a.add_(1)`` mutates ``x``), chaining
        through nested views.  Divergence (documented + tested): the READ
        direction is not aliased — a view materialized before a later
        base mutation keeps its copy; re-index to observe base updates.
        XLA arrays are immutable, so true two-way aliasing would need
        every ``_value`` read to re-slice the base."""
        base = self._view_base
        if base is not None:
            # pass the view ITSELF (differentiable): the base's setitem
            # then records the mutated value's autograd chain, so
            # x[0].add_(t); x.sum().backward() flows through the add —
            # wrapping a raw value would detach the region's gradient
            base[self._view_index] = self

    def set_value(self, value):
        """paddle Tensor.set_value — raw data replacement, no grad recording."""
        if isinstance(value, Tensor):
            value = value._value
        value = (value if isinstance(value, (jax.Array, jax.core.Tracer))
                 else jnp.array(value))  # copy external buffers (see __init__)
        if tuple(value.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch: {value.shape} vs {self._value.shape}"
            )
        self._value = value.astype(self._value.dtype)
        # rebind-style observer event: the new value came from OUTSIDE op
        # dispatch, so a partial-graph trace recorder must reject the trace
        # (a replay would silently reuse this call's data)
        from .dispatch import notify_inplace

        notify_inplace(self, "set_value", None)
        self._write_back_if_view()

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    def fill_(self, v):
        self._value = jnp.full_like(self._value, v)
        from .dispatch import notify_inplace

        # replayable: new value is a pure function of the old (v is a
        # baked constant, like any non-tensor op argument)
        notify_inplace(self, "fill_", lambda x: jnp.full_like(x, v))
        self._write_back_if_view()
        return self

    def zero_(self):
        self._value = jnp.zeros_like(self._value)
        from .dispatch import notify_inplace

        notify_inplace(self, "zero_", jnp.zeros_like)
        self._write_back_if_view()
        return self

    # --- indexing ------------------------------------------------------------
    def __getitem__(self, idx):
        from .dispatch import run_op

        idx = _unwrap_index(idx)
        out = run_op("getitem", lambda x: x[idx], self)
        if _is_basic_index(idx):
            # basic indexing is a VIEW in the reference (stride kernels);
            # mark it so in-place mutation writes back into this tensor.
            # Advanced indexing (arrays/bool masks) is a gather COPY in
            # the reference too — no link.
            out._view_base = self
            out._view_index = idx
        return out

    def __setitem__(self, idx, value):
        from .dispatch import run_op

        idx = _unwrap_index(idx)
        if isinstance(value, Tensor):
            out = run_op("setitem", lambda x, v: x.at[idx].set(v), self, value)
        else:
            out = run_op("setitem", lambda x: x.at[idx].set(value), self)
        self._rebind(out)

    # --- dunder math (implementations attached by paddle_tpu.tensor) --------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        return self._sync_scalar("bool")

    def __float__(self):
        return self._sync_scalar("float")

    def __int__(self):
        return self._sync_scalar("int")

    def __index__(self):
        return self._sync_scalar("int")

    def __hash__(self):
        return id(self)

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        if isinstance(self._value, jax.core.Tracer):
            return f"Tensor(shape={self.shape}, dtype={self.dtype}{grad_info}, traced)"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype}{grad_info},\n"
            f"       {self.numpy()})"
        )

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.numpy().item(), spec)
        return repr(self)


class Parameter(Tensor):
    """Trainable parameter (``stop_gradient=False`` by default).

    Analog of ``paddle.base.framework.EagerParamBase``.
    """

    __slots__ = ("optimize_attr", "regularizer", "need_clip", "is_distributed",
                 "sequence_parallel")

    def __init__(self, value, trainable: bool = True, name: Optional[str] = None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    if isinstance(idx, list):
        return jnp.asarray(idx)
    if isinstance(idx, np.bool_):
        # JAX's indexer takes a scalar mask only as the builtin bool
        return bool(idx)
    return idx


def _is_basic_index(idx) -> bool:
    """True for int/slice/Ellipsis/None (tuples thereof) — the indexing
    forms the reference serves as zero-copy stride VIEWS.  Array/bool
    indices are gather copies there too (bool subclasses int in BOTH
    type systems: reject it explicitly).  ``np.integer`` counts as int
    so ``x[np.int64(0)]`` is a write-back view like ``x[0]``, not a
    silent copy."""
    if isinstance(idx, tuple):
        return all(_is_basic_index(i) for i in idx)
    if isinstance(idx, (bool, np.bool_)):
        return False
    return (idx is None or idx is Ellipsis
            or isinstance(idx, (int, np.integer, slice)))


def wrap_result(out, stop_gradient: bool, node=None):
    """Wrap raw JAX output(s) into Tensor(s), wiring the grad node slot."""
    if isinstance(out, (list, tuple)):
        wrapped = []
        for i, o in enumerate(out):
            t = Tensor(o, stop_gradient=stop_gradient or not _inexact(o))
            if node is not None and not t.stop_gradient:
                t._grad_node = node
                t._out_index = i
            wrapped.append(t)
        return type(out)(wrapped)
    t = Tensor(out, stop_gradient=stop_gradient or not _inexact(out))
    if node is not None and not t.stop_gradient:
        t._grad_node = node
        t._out_index = 0
    return t


def _inexact(x) -> bool:
    try:
        return jnp.issubdtype(jnp.result_type(x), jnp.inexact)
    except Exception:
        return False


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """``paddle.to_tensor`` analog."""
    if isinstance(data, Tensor):
        if data._value is None:  # sparse facade — no dense payload
            raise RuntimeError(
                f"{type(data).__name__} holds no dense buffer; call "
                ".to_dense() before converting to a dense Tensor")
        v = data._value
    else:
        v = data
    d = dtype_mod.convert_dtype(dtype)
    if not isinstance(v, (jax.Array, jax.core.Tracer)):
        v = np.asarray(v)
        if d is None and v.dtype == np.float64:
            d = dtype_mod.get_default_dtype()
        v = jnp.array(v, dtype=d)  # copy external buffers (see __init__)
    elif d is not None:
        v = v.astype(d)
    return Tensor(v, stop_gradient=stop_gradient)
