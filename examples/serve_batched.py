"""Serve a small model with batched requests: prefill + streaming decode.

Demonstrates the production serving path (prefill_fast builds the KV/SSM
cache in one pass; decode_step advances every sequence one token) across
three cache families: dense GQA, sliding-window ring buffer, and O(1) SSM
state.

  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import make_model
from repro.serve.serving import generate

enable_compile_cache()

BATCH, PROMPT, NEW = 4, 24, 24

for arch in ["olmo-1b", "mixtral-8x7b", "mamba2-1.3b"]:
    run = get_smoke_config(arch)
    model = make_model(run.model)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PROMPT),
                                 0, run.model.vocab)
    t0 = time.time()
    out = generate(model, params, prompts, NEW, temperature=0.8,
                   key=jax.random.PRNGKey(2))
    dt = time.time() - t0
    kind = {"olmo-1b": "dense KV cache",
            "mixtral-8x7b": "sliding-window ring cache + MoE",
            "mamba2-1.3b": "O(1) SSM state"}[arch]
    print(f"{arch:14s} [{kind}] -> {out.shape}, "
          f"{BATCH*NEW/dt:6.1f} tok/s (incl. compile)")
    assert out.shape == (BATCH, PROMPT + NEW)
print("served all three cache families")
