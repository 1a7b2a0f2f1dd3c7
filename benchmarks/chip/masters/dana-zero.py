"""Plain DANA-Zero master (paper arXiv:1907.11612, Algorithm 4) replayed
over a fixed message order.

Worker i's message applies ``v_i <- gamma*v_i + g``, ``theta <- theta -
lr*v_i`` and answers with the look-ahead view ``theta - lr*gamma*sum_j
v_j``; every worker starts from ``theta0`` (all momenta are zero).  The
state is kept in ``dtype``: float32 as the configuration states, or
bfloat16 for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def replay(theta0, grad, steps, *, lr: float, momentum: float,
           workers: int, dtype=jnp.float32):
    """Apply ``steps`` — ``(worker, tokens)`` in arrival order — and
    return ``(theta, first_norms)``: the final parameters and the L2 norm
    of each leaf of the first gradient.  ``grad(view, tokens)`` is the
    worker's gradient at its current view."""
    cast = jax.jit(lambda t: jax.tree.map(lambda l: l.astype(dtype), t))

    @jax.jit
    def apply(theta, v_i, v_other, g):
        v_i = jax.tree.map(lambda a, b: (momentum * a + b.astype(dtype))
                           .astype(dtype), v_i, g)
        theta = jax.tree.map(lambda t, a: (t - lr * a).astype(dtype),
                             theta, v_i)
        v0 = jax.tree.map(lambda a, b: a + b, v_i, v_other)
        view = jax.tree.map(lambda t, s: (t - lr * momentum * s)
                            .astype(dtype), theta, v0)
        return theta, v_i, view

    theta = cast(theta0)
    zeros = jax.tree.map(jnp.zeros_like, theta)
    v = [zeros] * workers
    views = [theta] * workers
    first = None
    for w, tokens in steps:
        g = grad(views[w], tokens)
        if first is None:
            first = jax.tree.map(
                lambda l: jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)))),
                g)
        others = [v[j] for j in range(workers) if j != w]
        v_other = (jax.tree.map(lambda *xs: sum(xs), *others) if others
                   else zeros)
        theta, v[w], views[w] = apply(theta, v[w], v_other, g)
    return theta, first
