"""Parameter / ParameterDict — parity with ``python/mxnet/gluon/parameter.py``
(deferred init, grad_req, save/load, Trainer handoff).

Re-design vs the reference: the reference replicates each Parameter's data across the
Context list (`list_ctx`) for multi-GPU data parallelism; on TPU replication/sharding
is a *compiler annotation* (pjit shardings carried by ``Parameter.sharding``), so a
Parameter owns ONE logical NDArray. ``list_data``/``list_grad`` exist for API parity
and return single-element lists.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import initializer as init_mod
from ..base import dtype_np
from ..context import Context, current_context
from ..ndarray import ndarray as _nd
from ..ndarray.ndarray import NDArray
from ..observability import tracer


class DeferredInitializationError(RuntimeError):
    pass


class Parameter:
    """A trainable tensor with deferred initialization.

    ``shape`` may contain 0 (unknown) dims; the owning layer completes it at first
    forward (`_finish_deferred_init`), matching the reference's shape-inference flow
    (parameter.py:561 _finish_deferred_init).
    """

    def __init__(self, name: str, grad_req: str = "write", shape=None, dtype="float32",
                 lr_mult: float = 1.0, wd_mult: float = 1.0, init=None,
                 allow_deferred_init: bool = False, differentiable: bool = True,
                 stype: str = "default", grad_stype: str = "default",
                 keep_float32: bool = False):
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self.grad_req = grad_req if differentiable else "null"
        self.stype = stype
        self._data: Optional[NDArray] = None
        self._deferred_init: Optional[tuple] = None  # (init, ctx)
        self.sharding = None  # optional pjit PartitionSpec (TPU-first extension)
        # ``Block.cast`` leaves it as it is: a state, or a vector so small
        # that a narrower type's rounding would decide whether it ever moves
        self.keep_float32 = keep_float32

    # -- init --------------------------------------------------------------
    def _shape_complete(self) -> bool:
        return self.shape is not None and all(s > 0 for s in self.shape)

    def initialize(self, init=None, ctx: Optional[Context] = None,
                   default_init=None, force_reinit: bool = False):
        if self._data is not None and not force_reinit:
            return
        chosen = init or self.init or default_init or init_mod.Uniform()
        if not self._shape_complete():
            if not self.allow_deferred_init:
                raise ValueError(
                    f"Parameter {self.name}: shape {self.shape} incomplete and "
                    "deferred init not allowed")
            self._deferred_init = (chosen, ctx)
            return
        self._init_impl(chosen, ctx)

    def _init_impl(self, chosen, ctx):
        if self._data is not None and self._data.shape == tuple(self.shape):
            # force_reinit: keep the SAME handle so hybridized CachedOps (which
            # captured it) see the new values
            arr = self._data
            arr._set_data(jnp.zeros(self.shape, dtype_np(self.dtype)))
        else:
            arr = NDArray(jnp.zeros(self.shape, dtype_np(self.dtype)), ctx=ctx)
        init_mod.create(chosen).init_array(self.name, arr)
        self._data = arr
        self._deferred_init = None
        if self.grad_req != "null":
            self._data.attach_grad(self.grad_req)

    def _finish_deferred_init(self, shape: Tuple[int, ...]):
        """Complete unknown dims from the first forward's observed shape."""
        if self.shape is not None:
            merged = tuple(o if o > 0 else n for o, n in zip(self.shape, shape))
        else:
            merged = tuple(shape)
        self.shape = merged
        if self._deferred_init is not None:
            chosen, ctx = self._deferred_init
            self._init_impl(chosen, ctx)

    # -- access ------------------------------------------------------------
    def _check_initialized(self):
        if self._data is None:
            if self._deferred_init is not None or not self._shape_complete():
                raise DeferredInitializationError(
                    f"Parameter {self.name} deferred (shape {self.shape}); run a "
                    "forward pass or complete the shape first")
            raise RuntimeError(
                f"Parameter {self.name} has not been initialized; call "
                ".initialize() on the block or parameter first")

    def data(self, ctx: Optional[Context] = None) -> NDArray:
        self._check_initialized()
        return self._data

    def list_data(self) -> List[NDArray]:
        return [self.data()]

    def grad(self, ctx: Optional[Context] = None) -> NDArray:
        self._check_initialized()
        if self._data._grad is None:
            raise RuntimeError(f"Parameter {self.name} grad_req='null' — no gradient")
        return self._data._grad

    def list_grad(self) -> List[NDArray]:
        return [self.grad()]

    def list_ctx(self) -> List[Context]:
        self._check_initialized()
        return [self._data.context]

    def set_data(self, data):
        """Install given values (span ``param/set_data``: how often, and the
        seconds of the casts and reshapes it issues)."""
        with tracer.span("param/set_data"):
            if self._data is None:
                if self._deferred_init is not None:
                    self.shape = tuple(data.shape)
                    chosen, ctx = self._deferred_init
                    self._init_impl(chosen, ctx)
                else:
                    raise RuntimeError(
                        f"Parameter {self.name} not initialized")
            src = data if isinstance(data, NDArray) else NDArray(data)
            self._data._set_data(
                src.data.astype(self._data.dtype).reshape(self._data.shape))

    def zero_grad(self):
        if self._data is None or self._data._grad is None:
            return
        g = self._data._grad
        if getattr(g, "stype", "default") == "row_sparse":
            from ..ndarray import sparse as _sparse
            self._data._grad = _sparse.zeros("row_sparse", g.shape, dtype=g.dtype)
        else:
            g._set_data(jnp.zeros_like(g.data))

    def reset_ctx(self, ctx):
        pass  # single logical device; sharding handles placement

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            self._data._set_data(self._data.data.astype(dtype_np(dtype)))

    def var(self):
        raise NotImplementedError(
            "symbolic var() has no equivalent — hybridize traces the python forward")

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-trainable constant parameter (gluon.Constant parity)."""

    def __init__(self, name: str, value):
        value = value if isinstance(value, NDArray) else _nd.array(value)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.dtype))
        self._value = value
        self.init = init_mod.Constant(0)

    def _init_impl(self, chosen, ctx):
        self._data = NDArray(self._value.data, ctx=ctx)
        self._deferred_init = None


class ParameterDict:
    """Ordered name→Parameter mapping with prefix sharing (parameter.py:654)."""

    def __init__(self, prefix: str = "", shared: Optional["ParameterDict"] = None):
        self.prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self) -> Iterator[str]:
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, name: str) -> Parameter:
        return self._params[name]

    def __contains__(self, name: str) -> bool:
        return name in self._params

    def get(self, name: str, **kwargs) -> Parameter:
        """Create-or-retrieve by relative name (prefix applied), reference semantics."""
        full = self.prefix + name
        if full in self._params:
            param = self._params[full]
            for k, v in kwargs.items():
                if v is not None and getattr(param, k, None) in (None, 0):
                    setattr(param, k, v)
            return param
        if self._shared is not None and full in self._shared:
            param = self._shared[full]
        else:
            param = Parameter(full, **kwargs)
        self._params[full] = param
        return param

    def get_constant(self, name: str, value=None) -> Constant:
        full = self.prefix + name
        if full not in self._params:
            self._params[full] = Constant(full, value)
        return self._params[full]

    def update(self, other: "ParameterDict"):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError(f"duplicate parameter name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose: bool = False,
                   force_reinit: bool = False):
        for p in self.values():
            p.initialize(init=None, ctx=ctx, default_init=init,
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        pass

    def setattr(self, name: str, value):
        for p in self.values():
            setattr(p, name, value)

    def save(self, filename: str, strip_prefix: str = ""):
        arrays = {}
        for name, p in self.items():
            if p._data is None:
                continue
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            arrays[key] = p.data()
        _nd.save(filename, arrays)

    def load(self, filename: str, ctx=None, allow_missing: bool = False,
             ignore_extra: bool = False, restore_prefix: str = ""):
        loaded = _nd.load(filename)
        if isinstance(loaded, list):
            raise ValueError("expected a dict-style parameter file")
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                if name not in loaded:
                    raise ValueError(f"parameter {name} missing from {filename}")
        for name, arr in loaded.items():
            if name not in self._params:
                if ignore_extra:
                    continue
                raise ValueError(f"parameter {name} in file not in ParameterDict")
            p = self._params[name]
            if p._data is None:
                p.shape = tuple(arr.shape)
                p._deferred_init = p._deferred_init or (p.init, None)
                chosen, ctx_ = p._deferred_init
                p._init_impl(chosen or init_mod.Uniform(), ctx_)
            p.set_data(arr)

    def __repr__(self):
        lines = "\n".join(f"  {p!r}" for p in self.values())
        return f"ParameterDict(prefix={self.prefix!r}\n{lines}\n)"
