"""Basic layers — parity with ``python/mxnet/gluon/nn/basic_layers.py``:
Sequential/HybridSequential, Dense, Activation, Dropout, BatchNorm, LayerNorm,
InstanceNorm, Embedding, Flatten, Lambda/HybridLambda.
"""

from __future__ import annotations

from typing import Callable, Optional

from ... import autograd
from ... import ndarray as nd
from ..block import Block, HybridBlock
from ..parameter import Parameter


class Sequential(Block):
    """Stack of blocks run in order (dynamic)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __getitem__(self, key):
        return list(self._children.values())[key]

    def __len__(self):
        return len(self._children)


class HybridSequential(HybridBlock):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __getitem__(self, key):
        return list(self._children.values())[key]

    def __len__(self):
        return len(self._children)


class Dense(HybridBlock):
    """Fully-connected layer (basic_layers.py Dense → FullyConnected op)."""

    def __init__(self, units: int, activation: Optional[str] = None,
                 use_bias: bool = True, flatten: bool = True, dtype="float32",
                 weight_initializer=None, bias_initializer="zeros",
                 in_units: int = 0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._flatten = flatten
        self._act = activation
        self._use_bias = use_bias
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units, in_units),
                                          dtype=dtype, init=weight_initializer,
                                          allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get("bias", shape=(units,), dtype=dtype,
                                            init=bias_initializer,
                                            allow_deferred_init=True)

    def forward(self, x):
        if self.weight._data is None:
            in_units = 1
            if self._flatten:
                for s in x.shape[1:]:
                    in_units *= s
            else:
                in_units = x.shape[-1]
            self.weight._finish_deferred_init((self._units, in_units))
        if self._use_bias and self.bias._data is None:
            self.bias._finish_deferred_init((self._units,))
        out = nd.FullyConnected(x, self.weight.data(),
                                self.bias.data() if self._use_bias else None,
                                num_hidden=self._units, no_bias=not self._use_bias,
                                flatten=self._flatten)
        if self._act:
            out = nd.Activation(out, act_type=self._act)
        return out


class Activation(HybridBlock):
    def __init__(self, activation: str, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._act = activation

    def forward(self, x):
        return nd.Activation(x, act_type=self._act)


class LeakyReLU(HybridBlock):
    def __init__(self, alpha: float = 0.01, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._alpha = alpha

    def forward(self, x):
        return nd.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from ... import initializer
        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(0,),
                                         init=alpha_initializer or initializer.Constant(0.25),
                                         allow_deferred_init=True)

    def forward(self, x):
        if self.alpha._data is None:
            self.alpha._finish_deferred_init((x.shape[1] if x.ndim > 1 else 1,))
        return nd.LeakyReLU(x, self.alpha.data(), act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha: float = 1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._alpha = alpha

    def forward(self, x):
        return nd.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return nd.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def forward(self, x):
        return nd.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta: float = 1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._beta = beta

    def forward(self, x):
        return x * nd.sigmoid(self._beta * x)


class Dropout(HybridBlock):
    def __init__(self, rate: float, axes=(), prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        return nd.Dropout(x, p=self._rate, axes=self._axes)


class Flatten(HybridBlock):
    def forward(self, x):
        return nd.flatten(x)


class Lambda(Block):
    def __init__(self, function: Callable, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._fn = function if callable(function) else getattr(nd, function)

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function: Callable, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._fn = function if callable(function) else getattr(nd, function)

    def forward(self, *args):
        return self._fn(*args)


class Embedding(HybridBlock):
    """Embedding lookup; ``sparse_grad=True`` records a row-sparse weight gradient
    (gluon Embedding sparse_grad parity → lazy optimizer updates touch only the
    batch's rows; see ndarray/sparse.py). The sparse path is imperative-only — a
    hybridized block traces with the tape paused and falls back to dense grads."""

    def __init__(self, input_dim: int, output_dim: int, dtype="float32",
                 weight_initializer=None, sparse_grad: bool = False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._input_dim, self._output_dim = input_dim, output_dim
        self._sparse_grad = sparse_grad
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(input_dim, output_dim),
                                          dtype=dtype, init=weight_initializer,
                                          grad_stype="row_sparse" if sparse_grad
                                          else "default")

    def forward(self, x):
        from ... import autograd
        if not (self._sparse_grad and autograd.is_recording()):
            return nd.Embedding(x, self.weight.data(), input_dim=self._input_dim,
                                output_dim=self._output_dim)
        import jax.numpy as jnp
        from ...ndarray.ndarray import NDArray
        from ...ndarray.sparse import RawRowSparse
        w = self.weight.data()
        ids = x.data.astype(jnp.int32)
        out = NDArray(w.data[ids])
        wshape, outdim = w.shape, self._output_dim

        def backward_fn(saved, out_grads):
            (g,) = out_grads
            flat_ids = saved["ids"].reshape(-1)
            flat_g = g.reshape(-1, outdim)
            return [None, RawRowSparse(flat_ids, flat_g, wshape)]

        autograd.record_custom_node(None, [x, w], [out], backward_fn=backward_fn,
                                    saved={"ids": ids, "outs": [out.data]})
        return out


class BatchNorm(HybridBlock):
    """BatchNorm layer (basic_layers.py BatchNorm).

    Training uses batch stats and updates the running aux stats in place — the handle
    mutation is captured by the CachedOp trace as a state output (jit.py), replacing
    the reference's in-op aux-state writes (batch_norm.cc).
    """

    def __init__(self, axis: int = 1, momentum: float = 0.9, epsilon: float = 1e-5,
                 center: bool = True, scale: bool = True, use_global_stats: bool = False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 running_mean_initializer="zeros", running_variance_initializer="ones",
                 in_channels: int = 0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis, self._momentum, self._eps = axis, momentum, epsilon
        self._center, self._scale = center, scale
        self._use_global_stats = use_global_stats
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer,
                                         allow_deferred_init=True,
                                         differentiable=scale)
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        init=beta_initializer,
                                        allow_deferred_init=True,
                                        differentiable=center)
            self.running_mean = self.params.get("running_mean", shape=(in_channels,),
                                                init=running_mean_initializer,
                                                allow_deferred_init=True,
                                                differentiable=False)
            self.running_var = self.params.get("running_var", shape=(in_channels,),
                                               init=running_variance_initializer,
                                               allow_deferred_init=True,
                                               differentiable=False)

    def _finish(self, c):
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            if p._data is None:
                p._finish_deferred_init((c,))

    def forward(self, x):
        self._finish(x.shape[self._axis])
        gamma, beta = self.gamma.data(), self.beta.data()
        rmean, rvar = self.running_mean.data(), self.running_var.data()
        if autograd.is_training() and not self._use_global_stats:
            out, bmean, bvar = nd.batch_norm_train(
                x, gamma, beta, eps=self._eps, fix_gamma=not self._scale,
                axis=self._axis)
            m = self._momentum
            rmean._set_data((m * rmean.data + (1 - m) * bmean.data))
            rvar._set_data((m * rvar.data + (1 - m) * bvar.data))
            return out
        return nd.BatchNorm(x, gamma, beta, rmean, rvar, eps=self._eps,
                            fix_gamma=not self._scale, use_global_stats=True,
                            axis=self._axis)


class LayerNorm(HybridBlock):
    def __init__(self, axis: int = -1, epsilon: float = 1e-5, center: bool = True,
                 scale: bool = True, beta_initializer="zeros",
                 gamma_initializer="ones", in_channels: int = 0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis, self._eps = axis, epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer, allow_deferred_init=True,
                                         differentiable=scale)
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        init=beta_initializer, allow_deferred_init=True,
                                        differentiable=center)

    def forward(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if p._data is None:
                p._finish_deferred_init((c,))
        return nd.LayerNorm(x, self.gamma.data(), self.beta.data(), axis=self._axis,
                            eps=self._eps)


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * gamma``: no mean taken off, no bias."""

    def __init__(self, epsilon: float = 1e-5, in_channels: int = 0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="ones")

    def forward(self, x):
        return nd.contrib.rms_norm(x, self.gamma.data(), eps=self._eps)


class SwiGLU(HybridBlock):
    """``W_down (up * silu(gate))`` with ``[gate, up] = W_gu x``, no biases."""

    def __init__(self, units: int, ffn_units: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._ffn = ffn_units
        with self.name_scope():
            self.gate_up = Dense(2 * ffn_units, use_bias=False, flatten=False,
                                 in_units=units)
            self.down = Dense(units, use_bias=False, flatten=False,
                              in_units=ffn_units)

    def forward(self, x):
        gate_up = self.gate_up(x)
        gate = nd.slice_axis(gate_up, axis=-1, begin=0, end=self._ffn)
        up = nd.slice_axis(gate_up, axis=-1, begin=self._ffn,
                           end=2 * self._ffn)
        return self.down(up * nd.Activation(gate, act_type="silu"))


class InstanceNorm(HybridBlock):
    def __init__(self, axis: int = 1, epsilon: float = 1e-5, center: bool = True,
                 scale: bool = False, beta_initializer="zeros",
                 gamma_initializer="ones", in_channels: int = 0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer, allow_deferred_init=True,
                                         differentiable=scale)
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        init=beta_initializer, allow_deferred_init=True,
                                        differentiable=center)

    def forward(self, x):
        c = x.shape[1]
        for p in (self.gamma, self.beta):
            if p._data is None:
                p._finish_deferred_init((c,))
        return nd.InstanceNorm(x, self.gamma.data(), self.beta.data(), eps=self._eps)
