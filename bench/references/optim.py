"""Plain reference of the paper's optimizer, written from the paper alone.

PD-SGDM (Algorithm 1), per worker k and step t, with weight decay folded
into the gradient as in the paper's experiments:

    m ← μ m + (g + λ x);   x ← x − η m;   every p-th step  x_k ← Σ_j w_kj x_j

W is the ring's Metropolis matrix (1/3 to self and to each neighbour; a
pair averages, one worker keeps itself).  Everything here runs in float32.
Parameters are stored in the dtype the configuration states, momentum in
float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def ring_matrix(k: int) -> np.ndarray:
    if k == 1:
        return np.ones((1, 1))
    if k == 2:
        return np.full((2, 2), 0.5)
    w = np.zeros((k, k))
    for i in range(k):
        for j in (i - 1, i, i + 1):
            w[i, j % k] += 1.0 / 3.0
    return w


def mixing_matrix(topology: str, k: int) -> np.ndarray:
    if topology == "ring":
        return ring_matrix(k)
    if topology == "none":            # a fault: the exchange left out
        return np.eye(k)
    raise ValueError(f"no reference for topology {topology!r}")


def sgdm(x, m, g, *, lr, mu, wd):
    """One momentum step on one leaf; returns (x stored as before, m f32)."""
    x32 = x.astype(jnp.float32)
    m_new = mu * m + (g.astype(jnp.float32) + wd * x32)
    return (x32 - lr * m_new).astype(x.dtype), m_new


def leaf_norms(tree):
    """(n_leaves,) f32 norms of one worker's tree, in leaf order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree_util.tree_leaves(tree)])


def worker_leaf_norms(tree):
    """(K, n_leaves) f32 norms of a tree whose leaves lead with workers."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32).reshape(l.shape[0], -1)), axis=1))
        for l in jax.tree_util.tree_leaves(tree)], axis=1)
