"""The optimizers of the training configurations, in float32, from
their papers.  They import nothing of the program.

Adam is Kingma & Ba (arXiv:1412.6980), in the form of the end of their
section 2: the two bias corrections folded into the step size,
``lr_t = lr * sqrt(1 - b2**t) / (1 - b1**t)``.  SGD with momentum is the
classical form ``m = mu * m - lr * g; w = w + m`` (Sutskever et al.
2013, eq. 1-2), with no weight decay.
"""
import functools
import math

import jax
import jax.numpy as jnp


def adam_lr(lr, t, b1=0.9, b2=0.999):
    """The bias-corrected step size of update ``t`` (1-based)."""
    return lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)


def init_state(params, optimizer):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    if optimizer == "adam":
        return (zeros(), zeros())
    if optimizer == "sgd":
        return (zeros(),)
    raise ValueError(f"no reference for optimizer {optimizer!r}")


@functools.partial(jax.jit, static_argnames=("optimizer",),
                   donate_argnums=(0, 1))
def update(params, state, grads, lr_t, optimizer, momentum=0.9,
           b1=0.9, b2=0.999, eps=1e-8):
    """One update; ``lr_t`` is already the step size of this update."""
    tm = jax.tree_util.tree_map
    if optimizer == "adam":
        m = tm(lambda m, g: b1 * m + (1 - b1) * g, state[0], grads)
        v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, state[1], grads)
        new = tm(lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + eps),
                 params, m, v)
        return new, (m, v)
    m = tm(lambda m, g: momentum * m - lr_t * g, state[0], grads)
    return tm(jnp.add, params, m), (m,)
