"""Ablation: compression operator × consensus step γ × topology.

Beyond the paper's sign-only experiments: how Q's contraction δ and the
topology's spectral gap ρ trade off against bytes on the wire — the
quantities Corollary 2 couples through α = ρ²δ/82.  Every operator ships
its real wire-codec payload (``repro.core.wire``), so the comm-MB column
is the exact bytes a sharded run would move, not a model.

  PYTHONPATH=src python examples/compression_ablation.py

CI runs this as a smoke job with ``ABLATION_STEPS=8`` (trimmed steps —
same code path, just short).
"""
import os

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.core import (CPDSGDM, CPDSGDMConfig, IdentityCompressor,
                        QSGDCompressor, RandKCompressor, SignCompressor,
                        TopKCompressor)
from repro.core.gossip import DenseComm
from repro.core.topology import exponential, ring, torus
from repro.data.synthetic import LMStreamCfg, lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import make_model
from repro.train.trainer import SimTrainer

enable_compile_cache()

K = 8
STEPS = int(os.environ.get("ABLATION_STEPS", "50"))
model = make_model(ModelCfg(name="t", arch_type="dense", n_layers=2,
                            d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                            vocab=256))
params0 = jax.vmap(lambda _: model.init(jax.random.PRNGKey(0)))(
    jnp.arange(K))
data = LMStreamCfg(vocab=256, seq_len=32, batch=4, n_workers=K)

print(f"{'compressor':<14}{'topology':<13}{'gamma':>6}{'rho':>7}"
      f"{'final loss':>12}{'comm MB':>9}")
for comp, gamma in [(IdentityCompressor(), 0.4),
                    (SignCompressor(), 0.4),
                    (QSGDCompressor(levels=7), 0.4),
                    (TopKCompressor(fraction=0.1), 0.15),
                    (RandKCompressor(fraction=0.1), 0.1)]:
    for topo in [ring(K), exponential(K)]:
        opt = CPDSGDM(CPDSGDMConfig(eta=0.3, mu=0.9, p=4, gamma=gamma),
                      DenseComm(topo), comp)
        # fused rounds: each jitted call scans p local steps + one gossip
        trainer = SimTrainer(lambda p, b: model.loss(p, b), opt)
        _, _, h = trainer.train(params0, lambda t: lm_batch(data, t),
                                STEPS, log_every=max(STEPS - 1, 1))
        print(f"{comp.name:<14}{topo.name:<13}{gamma:>6.2f}{topo.rho:>7.3f}"
              f"{h.loss[-1]:>12.4f}{h.comm_mb[-1]:>9.2f}")
