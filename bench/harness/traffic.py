"""The one generator of training traffic, driven by ``traffic/<name>.json``.

Every batch is a pure function of the run's seed key and the step's index,
so the program and the reference see the same rows, and every row of every
step and worker differs.  One kind of data:

* ``lm_tokens`` — token sequences, ``per_worker_batch`` rows of
  ``seq_len`` tokens per worker and step, drawn uniformly from the
  vocabulary; the labels are the next tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# fold-in tag that keeps the data's keys apart from the weights' keys
_DATA = 0x5EED_DA7A % 2 ** 31


def step_batch(data: dict, key, step, n_workers: int, vocab: int):
    """One step's batch for every worker: leaves lead with (n_workers, ...)."""
    kind = data["kind"]
    k = jax.random.fold_in(jax.random.fold_in(key, _DATA), step)
    b = int(data["per_worker_batch"])
    if kind == "lm_tokens":
        s = int(data["seq_len"])
        toks = jax.random.randint(k, (n_workers, b, s + 1), 0, vocab,
                                  dtype=jnp.int32)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    raise ValueError(f"unknown traffic kind {kind!r}")


def round_batch(data: dict, key, rnd, p: int, n_workers: int, vocab: int):
    """A round's p steps stacked: leaves lead with (p, n_workers, ...)."""
    steps = [step_batch(data, key, rnd * p + i, n_workers, vocab)
             for i in range(p)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *steps)


def items_per_step(data: dict, n_workers: int) -> int:
    """Tokens trained per step by all workers."""
    return int(data["per_worker_batch"]) * n_workers * int(data["seq_len"])
