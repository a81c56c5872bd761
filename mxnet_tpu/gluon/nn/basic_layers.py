"""Core layers (reference python/mxnet/gluon/nn/basic_layers.py:144-700)."""
from __future__ import annotations

from typing import Optional

import numpy as _np

from ...base import MXNetError
from ..block import Block, HybridBlock, defer_aux_update
from ..parameter import Parameter


class Sequential(Block):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def forward(self, x):
        for b in self._children.values():
            x = b(x)
        return x

    def __getitem__(self, i):
        return list(self._children.values())[i]

    def __len__(self):
        return len(self._children)


class HybridSequential(HybridBlock):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def hybrid_forward(self, F, x):
        for b in self._children.values():
            x = b(x)
        return x

    def __getitem__(self, i):
        return list(self._children.values())[i]

    def __len__(self):
        return len(self._children)


class Dense(HybridBlock):
    """reference basic_layers.py:144 — weight (units, in_units)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._units = units
        self._flatten = flatten
        self._activation = activation
        self._use_bias = use_bias
        self.weight = self.params.get("weight", shape=(units, in_units),
                                      dtype=dtype, init=weight_initializer,
                                      allow_deferred_init=True)
        if use_bias:
            self.bias = self.params.get("bias", shape=(units,), dtype=dtype,
                                        init=bias_initializer,
                                        allow_deferred_init=True)
        else:
            self.bias = None

    def infer_shape(self, x, *args):
        in_units = int(_np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                               no_bias=(bias is None), flatten=self._flatten)
        if self._activation:
            out = F.Activation(out, act_type=self._activation)
        return out


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), prefix=None, params=None):
        super().__init__(prefix, params)
        self._rate = rate
        self._axes = tuple(axes)

    def hybrid_forward(self, F, x):
        from ... import autograd
        return F.Dropout(x, p=self._rate, axes=self._axes,
                         training=autograd.is_training() or autograd.is_recording())


class BatchNorm(HybridBlock):
    """reference basic_layers.py:282 — running stats updated via
    defer_aux_update (functional under traces)."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self.gamma = self.params.get("gamma", shape=(in_channels,), init=gamma_initializer,
                                     allow_deferred_init=True,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,), init=beta_initializer,
                                    allow_deferred_init=True,
                                    grad_req="write" if center else "null")
        self.running_mean = self.params.get("running_mean", shape=(in_channels,),
                                            init=running_mean_initializer,
                                            allow_deferred_init=True, grad_req="null",
                                            differentiable=False)
        self.running_var = self.params.get("running_var", shape=(in_channels,),
                                           init=running_variance_initializer,
                                           allow_deferred_init=True, grad_req="null",
                                           differentiable=False)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def cast(self, dtype):
        # keep stats in f32 (TPU numerics)
        import jax.numpy as jnp
        if jnp.dtype(dtype) in (jnp.float16, jnp.bfloat16):
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        from ... import autograd
        training = (autograd.is_training() or autograd.is_recording()) \
            and not self._use_global_stats
        out, mean, var = F.BatchNorm(
            x, gamma, beta, running_mean, running_var, eps=self._epsilon,
            momentum=self._momentum, fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats, axis=self._axis,
            training=training)
        if training:
            m = self._momentum
            defer_aux_update(self.running_mean,
                             m * running_mean._data + (1 - m) * mean._data)
            defer_aux_update(self.running_var,
                             m * running_var._data + (1 - m) * var._data)
        return out


class SyncBatchNorm(BatchNorm):
    """Cross-device BN (reference contrib sync_batch_norm). On TPU the batch
    axis is sharded by the mesh; under pjit/shard_map the mean/var reductions
    become cross-replica automatically, so this is BatchNorm + a note."""

    def __init__(self, in_channels=0, num_devices=None, **kwargs):
        kwargs.setdefault("prefix", None)
        super().__init__(in_channels=in_channels, **kwargs)


class Embedding(HybridBlock):
    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, prefix=None, params=None):
        super().__init__(prefix, params)
        self._input_dim = input_dim
        self._output_dim = output_dim
        # sparse_grad marks the weight's gradient row_sparse so optimizers
        # with lazy_update skip rows absent from the batch (reference
        # gluon/nn/basic_layers.py Embedding(sparse_grad=True))
        self.weight = self.params.get(
            "weight", shape=(input_dim, output_dim), dtype=dtype,
            init=weight_initializer,
            grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.Flatten(x)


class Activation(HybridBlock):
    def __init__(self, activation, prefix=None, params=None):
        super().__init__(prefix, params)
        self._act_type = activation

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)


class LeakyReLU(HybridBlock):
    def __init__(self, alpha, prefix=None, params=None):
        super().__init__(prefix, params)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, in_channels=1, prefix=None, params=None):
        super().__init__(prefix, params)
        from ... import initializer
        self.alpha = self.params.get("alpha", shape=(in_channels,),
                                     init=alpha_initializer or initializer.Constant(0.25))

    def hybrid_forward(self, F, x, alpha):
        a = alpha.reshape((1, -1) + (1,) * max(x.ndim - 2, 0)) if x.ndim > 1 else alpha
        return F.broadcast_maximum(x, x * 0) + F.broadcast_minimum(x, x * 0) * a


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def __init__(self, approximate=False, prefix=None, params=None):
        super().__init__(prefix, params)
        self._approximate = approximate

    def hybrid_forward(self, F, x):
        return F.gelu(x, approximate=self._approximate)


class Swish(HybridBlock):
    def __init__(self, beta=1.0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)


class LayerNorm(HybridBlock):
    """reference basic_layers.py:546."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._axis = axis
        self._epsilon = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer, allow_deferred_init=True,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer, allow_deferred_init=True,
                                    grad_req="write" if center else "null")

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class RMSNorm(HybridBlock):
    """gamma * v * rsqrt(mean(v^2) + eps) over the last axis, computed in
    float32 (ops/nn.py `RMSNorm`). Called with a second input it is the
    gated form, v = x * silu(gate), that a Mamba-2 mixer puts in front of
    its output projection. No reference counterpart."""

    def __init__(self, epsilon=1e-5, gamma_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        self._epsilon = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer,
                                     allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[-1],)

    def hybrid_forward(self, F, x, gate=None, gamma=None):
        return F.RMSNorm(x, gamma, gate, eps=self._epsilon)


class GroupNorm(HybridBlock):
    """reference basic_layers.py:630."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer, allow_deferred_init=True,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer, allow_deferred_init=True,
                                    grad_req="write" if center else "null")

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[1],)
        self.beta.shape = (x.shape[1],)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._epsilon = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer, allow_deferred_init=True)
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer, allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[1],)
        self.beta.shape = (x.shape[1],)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)


class Lambda(Block):
    def __init__(self, function, prefix=None):
        super().__init__(prefix)
        if isinstance(function, str):
            from ... import ndarray as nd_mod
            function = getattr(nd_mod, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None):
        super().__init__(prefix)
        self._func_name = function if isinstance(function, str) else function.__name__
        self._func = function

    def hybrid_forward(self, F, *args):
        if isinstance(self._func, str):
            return getattr(F, self._func)(*args)
        return self._func(F, *args)


class Identity(HybridBlock):
    def hybrid_forward(self, F, x):
        return x
