"""Quickstart: decentralized momentum SGD (PD-SGDM) in ~40 lines.

8 workers on a ring train a tiny LM with local momentum steps and gossip
every p=4 iterations; the same run with sign-compressed gossip (CPD-SGDM)
shows the ~30× communication saving at matching loss; and a time-varying
one-peer exponential topology halves the bytes of the ring again (degree 1
per round) while its 3-round cycle mixes like a hypercube.

Execution goes through the fused round engine: each jitted call runs a
``lax.scan`` of whole rounds (p local steps + one gossip), syncing the
host once per log block instead of once per step.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.core import (CPDSGDMConfig, CPDSGDM, PDSGDM, PDSGDMConfig,
                        SignCompressor)
from repro.core.gossip import DenseComm
from repro.core.topology import one_peer_exponential_schedule, ring
from repro.data.synthetic import LMStreamCfg, lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import make_model
from repro.train.trainer import SimTrainer

enable_compile_cache()

K = 8       # workers on a ring (the paper's setup)
STEPS = 60

model = make_model(ModelCfg(
    name="tiny-lm", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=256))

# every worker starts from the same x0 (Algorithm 1 input)
params0 = jax.vmap(lambda _: model.init(jax.random.PRNGKey(0)))(
    jnp.arange(K))
data = LMStreamCfg(vocab=256, seq_len=32, batch=4, n_workers=K)

for label, opt in [
    ("PD-SGDM  (Alg.1, full-precision gossip)",
     PDSGDM(PDSGDMConfig(eta=0.3, mu=0.9, p=4), DenseComm(ring(K)))),
    ("CPD-SGDM (Alg.2, 1-bit sign gossip)",
     CPDSGDM(CPDSGDMConfig(eta=0.3, mu=0.9, p=4, gamma=0.4),
             DenseComm(ring(K)), SignCompressor())),
    ("PD-SGDM  (one-peer exponential schedule, degree 1)",
     PDSGDM(PDSGDMConfig(eta=0.3, mu=0.9, p=4),
            DenseComm(one_peer_exponential_schedule(K)))),
]:
    trainer = SimTrainer(lambda p, b: model.loss(p, b), opt,
                         rounds_per_log=5)   # 5 rounds = 20 steps per sync
    _, _, hist = trainer.train(params0, lambda t: lm_batch(data, t),
                               steps=STEPS, log_every=20)
    print(f"{label}\n  loss {hist.loss[0]:.3f} -> {hist.loss[-1]:.3f}   "
          f"communicated {hist.comm_mb[-1]:.2f} MB over "
          f"{STEPS // opt.config.p} rounds\n")
