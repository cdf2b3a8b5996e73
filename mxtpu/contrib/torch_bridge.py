"""Torch plugin bridge — run PyTorch (CPU) code as first-class framework ops.

Reference capability: ``plugin/torch`` (TorchModule/torch criterion as MXNet
operators; mxnet.torch namespace) — users bring a foreign framework's kernels
into the graph. The TPU-native analog: ``register_torch_op`` wraps a torch
function as a REAL registry op — visible as ``mx.nd.<name>`` and
``mx.sym.<name>``, usable eagerly, inside ``hybridize``/``jit`` (it lowers to
``jax.pure_callback``, so the torch code runs host-side while the surrounding
program stays compiled), and differentiable: the backward is computed by
``torch.autograd`` inside a second callback, spliced in via ``jax.custom_vjp``.

This is the same machinery as ``mxtpu.operator.CustomOp`` (custom-inl.h role),
pointed at torch instead of user numpy — proving the escape hatch composes
with a real foreign framework.

Constraints (documented, reference-parity): the torch fn must be a pure
tensor→tensor(s) function (no hidden state), CPU torch, float tensors.
"""

from __future__ import annotations

from typing import Callable, Dict, Sequence, Tuple

import numpy as np

__all__ = ["register_torch_op", "TorchOp"]


def _require_torch():
    try:
        import torch
    except ImportError as e:  # pragma: no cover - torch is baked into the image
        raise ImportError("the torch bridge needs pytorch installed") from e
    return torch


class TorchOp:
    """A torch function wrapped as a differentiable jax-compatible callable."""

    def __init__(self, fn: Callable, name: str = "torch_op"):
        self.fn = fn
        self.name = name
        self._out_struct: Dict[tuple, tuple] = {}  # sig -> (shapes, dtypes, single)
        self._build()

    # -- host-side executions (inside pure_callback) -----------------------
    @staticmethod
    def _to_torch(torch, a):
        a = np.ascontiguousarray(a)
        if not a.flags.writeable:       # jax buffers are read-only views
            a = a.copy()
        return torch.from_numpy(a)

    def _run_fwd(self, *arrays):
        torch = _require_torch()
        with torch.no_grad():
            outs = self.fn(*[self._to_torch(torch, a) for a in arrays])
        single = not isinstance(outs, (tuple, list))
        outs = [outs] if single else list(outs)
        return [o.detach().numpy() for o in outs], single

    def _run_bwd(self, arrays, cots):
        torch = _require_torch()
        tins = [self._to_torch(torch, a).requires_grad_(True)
                for a in arrays]
        outs = self.fn(*tins)
        outs = [outs] if not isinstance(outs, (tuple, list)) else list(outs)
        gouts = [self._to_torch(torch, c) for c in cots]
        grads = torch.autograd.grad(outs, tins, grad_outputs=gouts,
                                    allow_unused=True)
        return [np.zeros(a.shape, a.dtype) if g is None else
                g.detach().numpy().astype(a.dtype, copy=False)
                for g, a in zip(grads, arrays)]

    def _struct_for(self, args) -> tuple:
        """Output (shapes, dtypes, single) per input signature — probed once by
        running the torch fn on zeros host-side (the fn must be shape-pure)."""
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
        got = self._out_struct.get(sig)
        if got is None:
            probe = [np.zeros(s, np.dtype(d)) for s, d in sig]
            outs, single = self._run_fwd(*probe)
            got = (tuple(o.shape for o in outs),
                   tuple(o.dtype for o in outs), single)
            self._out_struct[sig] = got
        return got

    # -- the jax-facing callable -------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp

        op = self

        @jax.custom_vjp
        def call(*args):
            shapes, dtypes, _ = op._struct_for(args)
            result_shape = tuple(jax.ShapeDtypeStruct(s, d)
                                 for s, d in zip(shapes, dtypes))
            outs = jax.pure_callback(
                lambda *a: tuple(op._run_fwd(*[np.asarray(x) for x in a])[0]),
                result_shape, *args, vmap_method="sequential")
            return outs

        def fwd(*args):
            return call(*args), args

        def bwd(res, cots):
            in_struct = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                              for a in res)
            grads = jax.pure_callback(
                lambda inputs, gs: tuple(op._run_bwd(
                    [np.asarray(x) for x in inputs],
                    [np.asarray(g) for g in gs])),
                in_struct, res, cots, vmap_method="sequential")
            return tuple(grads)

        call.defvjp(fwd, bwd)
        self._call = call

    def __call__(self, *args):
        import jax.numpy as jnp
        raw = [a.data if hasattr(a, "data") and not isinstance(a, np.ndarray)
               else jnp.asarray(a) for a in args]
        outs = self._call(*raw)
        _, _, single = self._struct_for(raw)
        return outs[0] if single else tuple(outs)


def register_torch_op(name: str, fn: Callable, namespace: str = "contrib",
                      num_outputs: int = 1):
    """Register ``fn`` (torch tensors in → tensor(s) out) as a framework op.

    After this, ``mx.nd.contrib.<name>`` / ``mx.sym.contrib.<name>`` exist like
    any built-in op (mxnet.torch namespace parity). Returns the TorchOp.
    Multi-output fns must declare ``num_outputs`` so the symbolic frontend
    exposes every head (the nd path detects the tuple dynamically).
    """
    from ..ops import registry as _reg

    top = TorchOp(fn, name)

    def op_fn(*args):
        outs = top._call(*args)
        # single-ness is static per input signature (probed host-side), so
        # this branch resolves at trace time
        _, _, single = top._struct_for(args)
        return outs[0] if single else outs

    op_fn.__name__ = name
    op_fn.__doc__ = f"torch-bridge op {name!r} (plugin/torch parity)"
    _reg.register(f"{namespace}.{name}" if namespace else name,
                  num_outputs=num_outputs)(op_fn)

    # surface on the already-built nd/sym namespaces
    from .. import ndarray as nd_pkg
    from .. import symbol as sym_pkg
    from ..symbol.symbol import make_op_wrapper
    key = f"{namespace}.{name}" if namespace else name
    opdef = _reg.get_op(key)

    def nd_wrapper(*args, **kwargs):
        return _reg.invoke(opdef, *args, **kwargs)

    nd_wrapper.__name__ = name
    target_nd = getattr(nd_pkg, namespace) if namespace else nd_pkg
    target_sym = getattr(sym_pkg, namespace) if namespace else sym_pkg
    setattr(target_nd, name, nd_wrapper)
    setattr(target_sym, name, make_op_wrapper(key))
    return top
