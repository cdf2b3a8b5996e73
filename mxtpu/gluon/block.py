"""Block / HybridBlock — parity with ``python/mxnet/gluon/block.py``.

* ``Block`` (block.py:126): dynamic imperative module with auto-registered children
  and parameters, name scoping, ``collect_params``, ``save/load_parameters``.
* ``HybridBlock`` (block.py:536): callable both imperatively and compiled.
  ``hybridize()`` in the reference traces ``hybrid_forward`` with symbol proxies into
  a ``CachedOp`` (block.py:746 ``_build_cache``); here the SAME python forward is traced
  by ``jax.jit`` through ``mxtpu.jit.CachedOp`` — no symbol language needed, and the
  trace recompiles automatically per input signature (shape bucketing).
* ``export`` writes params + StableHLO text (≈ symbol JSON + params, block.py:866).

``hybrid_forward(F, x, ...)`` is supported for reference-style subclasses (``F`` is
``mxtpu.nd``); idiomatic subclasses may instead override ``forward(x)`` directly and
read ``self.<param>.data()``.
"""

from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from .. import ndarray as nd_mod
from ..jit import CachedOp, export_stablehlo
from ..ndarray.ndarray import NDArray
from ..observability import metrics, tracer
from .parameter import Parameter, ParameterDict

_name_counter = threading.local()


class _BlockScope:
    """Hierarchical name manager (block.py _BlockScope parity)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter: Dict[str, int] = {}
        self._old = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                if not hasattr(_name_counter, "counts"):
                    _name_counter.counts = {}
                cnt = _name_counter.counts.get(hint, 0)
                _name_counter.counts[hint] = cnt + 1
                prefix = f"{hint}{cnt}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, shared=params)
            return prefix, params
        if prefix is None:
            cnt = current._counter.get(hint, 0)
            current._counter[hint] = cnt + 1
            prefix = f"{hint}{cnt}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, shared=None)
        else:
            params = ParameterDict(params.prefix, shared=params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        self._old = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        _BlockScope._current.value = self._old
        return False


@contextlib.contextmanager
def _outermost(name: str, opener):
    """``opener(name)`` (``tracer.span`` or ``metrics.marked_span``), or
    nothing where a span of that name is open on the thread already: the
    seconds of a name then add up to wall time."""
    if tracer.is_open(name):
        yield
        return
    with opener(name):
        yield


class Block:
    """Base neural-network module (gluon.Block parity)."""

    def __init__(self, prefix: Optional[str] = None, params: Optional[ParameterDict] = None):
        hint = re.sub(r"(?<!^)(?=[A-Z])", "", type(self).__name__).lower()
        self._prefix, self._params = _BlockScope.create(prefix, params, hint)
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        # the device scope ``forward`` runs under: the name the parent
        # registers this block under (``block3``, ``attn``, ``ffn1``), the
        # class name for a root. Not the prefix: its counters differ from
        # one instance to the next, and a trace reader's pattern must not
        self._scope_name = type(self).__name__
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._forward_hooks: List[Callable] = []
        self._forward_pre_hooks: List[Callable] = []

    # -- registration ------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                value._scope_name = name
        elif isinstance(value, Parameter):
            params = self.__dict__.get("_params")
            if params is not None:
                params._params[value.name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None):
        name = name or str(len(self._children))
        self._children[name] = block
        block._scope_name = name

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    # -- properties --------------------------------------------------------
    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self) -> _BlockScope:
        return self._scope

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            for name, p in self._params.items():
                if pat.match(name):
                    ret._params[name] = p
        for child in self._children.values():
            sub = child.collect_params(select)
            for name, p in sub.items():
                ret._params[name] = p
        return ret

    # -- lifecycle ---------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose: bool = False,
                   force_reinit: bool = False):
        """Span ``net/initialize`` around the OUTERMOST call (a block whose
        own ``initialize`` goes through its children's opens none again),
        with a memory mark at its end."""
        with _outermost("net/initialize", metrics.marked_span):
            self.collect_params().initialize(
                init=init, ctx=ctx, verbose=verbose,
                force_reinit=force_reinit)
        return self

    def cast(self, dtype):
        """Every parameter to ``dtype`` but those made with
        ``keep_float32``. Before ``initialize`` this only sets the type the
        parameters will be made in. Span ``net/cast`` around the outermost
        call."""
        with _outermost("net/cast", tracer.span):
            for p in self.collect_params().values():
                if not p.keep_float32:
                    p.cast(dtype)
        return self

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- serialization -----------------------------------------------------
    def save_parameters(self, filename: str):
        """block.py:313 save_parameters — strips the block prefix like the reference."""
        params = self.collect_params()
        arrays = {}
        for name, p in params.items():
            if p._data is None:
                continue
            key = name[len(self.prefix):] if name.startswith(self.prefix) else name
            arrays[key] = p.data()
        nd_mod.save(filename, arrays)

    def load_parameters(self, filename: str, ctx=None, allow_missing: bool = False,
                        ignore_extra: bool = False):
        loaded = nd_mod.load(filename)
        params = self.collect_params()
        restored = {}
        for k, v in loaded.items():
            full = k if k in params else self.prefix + k
            restored[full] = v
        if not allow_missing:
            for name, p in params.items():
                if name not in restored:
                    raise ValueError(f"parameter {name} missing from {filename}")
        for name, arr in restored.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise ValueError(f"parameter {name} from file not found in block")
            p = params[name]
            if p.shape is not None:
                # declared dims must match the file (0 = deferred, adopts file dim)
                if len(p.shape) != arr.ndim or any(
                        s > 0 and s != f for s, f in zip(p.shape, arr.shape)):
                    raise ValueError(
                        f"parameter {name}: declared shape {p.shape} incompatible "
                        f"with loaded shape {arr.shape}")
            if p._data is None:
                from .. import initializer
                p.shape = tuple(arr.shape)
                p._init_impl(p.init or initializer.Zero(), None)
            p.set_data(arr)

    # legacy-name parity (block.py save_params/load_params deprecated aliases)
    save_params = save_parameters
    load_params = load_parameters

    # -- execution ---------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self._scoped_forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def _scoped_forward(self, *args, **kwargs):
        """``forward`` under this block's ``jax.named_scope``: metadata on
        every operation traced inside (``block3/attn/q_proj/...``), which
        the compiled program and the device trace carry; adds no operation."""
        with jax.named_scope(self._scope_name):
            return self.forward(*args, **kwargs)

    def hybridize(self, active: bool = True, **kwargs):
        """No-op on plain Blocks except recursing into children (reference parity)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        out = self(*inputs)
        n_params = sum(int(np_prod(p.shape)) for p in self.collect_params().values()
                       if p.shape)
        print(f"{type(self).__name__}: params={n_params}")
        return out

    def __repr__(self):
        lines = [f"{type(self).__name__}("]
        for name, child in self._children.items():
            lines.append(f"  ({name}): {type(child).__name__}")
        lines.append(")")
        return "\n".join(lines)


def np_prod(shape):
    out = 1
    for s in shape:
        out *= s
    return out


class HybridBlock(Block):
    """Block that can run compiled (gluon.HybridBlock parity; jit.CachedOp backend)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op: Optional[CachedOp] = None
        self._flags: Dict[str, Any] = {}

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           **kwargs)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _ensure_params_ready(self, args):
        """Finish deferred shape inference by one imperative dry-run if needed."""
        params = self.collect_params()
        if any(p._data is None for p in params.values()):
            # run imperatively once: layers complete their own deferred params
            self.forward(*args)

    def __call__(self, *args, **kwargs):
        if self._active and kwargs:
            # keyword/optional-arg calls fall back to the imperative path (the
            # CachedOp trace covers the positional signature)
            return super().__call__(*args, **kwargs)
        if self._active:
            args = [a if isinstance(a, NDArray) else nd_mod.array(a) for a in args]
            if self._cached_op is None:
                self._ensure_params_ready(args)
                params = [p.data() for p in self.collect_params().values()
                          if p._data is not None]
                self._cached_op = CachedOp(
                    self._scoped_forward, params=params,
                    static_alloc=self._flags.get("static_alloc", False),
                    static_shape=self._flags.get("static_shape", False))
            return self._cached_op(*args)
        return super().__call__(*args, **kwargs)

    def forward(self, *args):
        """Default: dispatch to reference-style ``hybrid_forward(F, x, **params)``."""
        if hasattr(self, "hybrid_forward"):
            params = {}
            for name, p in self._params.items():
                short = name[len(self.prefix):] if name.startswith(self.prefix) else name
                try:
                    params[short] = p.data()
                except Exception:
                    p._finish_deferred_init(self._infer_param_shape(short, p, args))
                    params[short] = p.data()
            return self.hybrid_forward(nd_mod, *args, **params)
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward or hybrid_forward")

    def _infer_param_shape(self, short_name, param, args):
        raise NotImplementedError(
            f"cannot infer deferred shape for {param.name}; initialize with a "
            "complete shape or implement shape inference in the layer")

    def export(self, path: str, epoch: int = 0):
        """StableHLO + params export (≈ block.py:866 export to symbol-json+params):
        writes ``path-####.params`` and ``path-symbol.stablehlo.txt`` (real StableHLO
        of the first traced signature)."""
        if self._cached_op is None or not self._cached_op._cache:
            raise RuntimeError("export requires a hybridized block that has run once")
        self.save_parameters(f"{path}-{epoch:04d}.params")
        import jax.numpy as jnp
        from ..base import dtype_np
        sig = next(iter(self._cached_op._cache))
        arg_shapes = sig[0]  # ((shape, dtype, sharding), ...) per input
        examples = [NDArray(jnp.zeros(s, dtype_np(dt)))
                    for s, dt, *_rest in arg_shapes]
        from .. import autograd as _ag
        with _ag.predict_mode():
            text = export_stablehlo(lambda *xs: self.forward(*xs), examples)
        with open(f"{path}-symbol.stablehlo.txt", "w") as f:
            f.write(text)
        return path

    def infer_shape(self, *args):
        self._ensure_params_ready([a if isinstance(a, NDArray) else nd_mod.array(a)
                                   for a in args])


class SymbolBlock(HybridBlock):
    """Gluon block over a Symbol graph (block.py:950 SymbolBlock parity).

    ``outputs`` is a Symbol (or list → Group); ``inputs`` names the free variables
    fed by ``forward(*args)``; every other argument becomes a Parameter (exact
    symbol name, deferred shape completed by ``infer_shape`` at first forward).
    Forward evaluates the DAG on raw arrays and records ONE tape node whose replay
    closure reuses the forward's resolved RNG/flag state — the same single-node
    contract the CachedOp path uses (autograd.record_custom_node).
    """

    def __init__(self, outputs, inputs, params=None, prefix=None):
        super().__init__(prefix=prefix)
        from ..symbol import Group, Symbol
        from ..symbol.symbol import _AUX_PARAMS  # noqa: F401 (doc pointer)
        if isinstance(outputs, (list, tuple)):
            outputs = Group(list(outputs))
        self._sym = outputs
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        self._input_names = [i if isinstance(i, str) else i.name for i in inputs]
        arg_names = outputs.list_arguments()
        aux_names = outputs.list_auxiliary_states()
        self._sym_param_names = [n for n in arg_names
                                 if n not in self._input_names] + aux_names
        given = dict(params.items()) if params is not None else {}
        for n in self._sym_param_names:
            if n in given:
                self._params._params[n] = given[n]
            else:
                self._params._params[n] = Parameter(
                    n, shape=None, allow_deferred_init=True,
                    grad_req="null" if n in aux_names else "write")
        self._shapes_done = False

    @staticmethod
    def imports(symbol_file: str, input_names, param_file: Optional[str] = None,
                ctx=None):
        """Load an exported (symbol-json, params) pair (SymbolBlock.imports parity)."""
        from .. import symbol as sym_mod
        from .. import ndarray as nd_mod
        net = SymbolBlock(sym_mod.load(symbol_file), input_names)
        if param_file is not None:
            loaded = nd_mod.load(param_file)
            for name, arr in loaded.items():
                short = name.split(":", 1)[1] if ":" in name else name
                if short in net._params._params:
                    p = net._params._params[short]
                    p.shape = tuple(arr.shape)
                    p._init_impl(p.init or "zeros", None)
                    p.set_data(arr)
        return net

    def _complete_shapes(self, args):
        from ..symbol.symbol import _req_of  # noqa: F401
        shapes = {n: tuple(a.shape) for n, a in zip(self._input_names, args)}
        arg_shapes, _, aux_shapes = self._sym.infer_shape(**shapes)
        arg_names = self._sym.list_arguments()
        aux_names = self._sym.list_auxiliary_states()
        for n, s in list(zip(arg_names, arg_shapes)) + \
                list(zip(aux_names, aux_shapes)):
            if n in self._params._params and s is not None:
                p = self._params._params[n]
                if p._data is None:
                    p._finish_deferred_init(s)
                    if p._data is None:  # initialize() never called on the block
                        p.shape = tuple(s)
                        p.initialize()
        self._shapes_done = True

    def forward(self, *args):
        from .. import autograd
        from ..symbol.symbol import eval_graph
        if not self._shapes_done:
            self._complete_shapes(args)
        param_handles = [self._params._params[n].data()
                         for n in self._sym_param_names]
        names = self._input_names + self._sym_param_names
        feed = {n: a.data for n, a in
                zip(names, list(args) + param_handles)}
        resolved: dict = {}
        aux_updates: dict = {}
        is_train = autograd.is_training()
        with autograd.pause(train_mode=is_train):
            outs_raw = eval_graph(self._sym._heads, feed, is_train,
                                  aux_updates=aux_updates, resolved=resolved)
        outs = [NDArray(o) for o in outs_raw]
        if autograd.is_recording():
            heads = self._sym._heads

            def pure_fn(*raws):
                feed2 = dict(zip(names, raws))
                res = eval_graph(heads, feed2, is_train, resolved=resolved)
                return tuple(res) if len(res) > 1 else res[0]

            autograd.record_custom_node(pure_fn, list(args) + param_handles, outs)
        for name, new in aux_updates.items():
            if name in self._params._params:
                self._params._params[name].data()._set_data(new)
        return outs[0] if len(outs) == 1 else tuple(outs)
