"""Adam (reference: ``python/paddle/optimizer/adam.py``; kernel semantics
``paddle/phi/kernels/impl/adam_kernel_impl.h``)."""
from __future__ import annotations

import jax.numpy as jnp

from .optimizer import Optimizer

__all__ = ["Adam"]


class Adam(Optimizer):
    """Paddle's documented rule::

        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g*g
        lr_t = lr * sqrt(1 - beta2^t) / (1 - beta1^t)
        param = param - lr_t * m / (sqrt(v) + eps)
    """

    _group_opts = ("beta1", "beta2", "epsilon")
    _fusable_update = True  # elementwise: safe over concatenated buffers

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = float(beta1)
        self._beta2 = float(beta2)
        self._epsilon = float(epsilon)

    def _create_state(self, p):
        dt = jnp.float32 if self._needs_master(p) else p.data.dtype
        return {
            # zeros_like: the moments are born with the parameter's
            # sharding instead of whole on the default device
            "moment1": jnp.zeros_like(p.data, dtype=dt),
            "moment2": jnp.zeros_like(p.data, dtype=dt),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _update_delta(self, grad, state, lr, beta1=0.9, beta2=0.999,
                      epsilon=1e-8):
        m = beta1 * state["moment1"] + (1 - beta1) * grad
        v = beta2 * state["moment2"] + (1 - beta2) * grad * grad
        b1p = state["beta1_pow"] * beta1
        b2p = state["beta2_pow"] * beta2
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        delta = lr_t * m / (jnp.sqrt(v) + epsilon)
        ns = dict(state)
        ns.update(moment1=m, moment2=v, beta1_pow=b1p, beta2_pow=b2p)
        return delta, ns
