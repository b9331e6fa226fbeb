"""jaxpr-level round-contract checks: green on the real optimizers, and
each check catches its seeded violation (negative tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_check as jc
from repro.core import (CPDSGDM, CPDSGDMConfig, PDSGDM, PDSGDMConfig,
                        SignCompressor, make_optimizer)
from repro.core.gossip import DenseComm
from repro.core.topology import make_schedule, ring

K = 8


def _pd(p=3, **kw):
    return PDSGDM(PDSGDMConfig(eta=0.05, mu=0.9, p=p, **kw), DenseComm(ring(K)))


# ------------------------------------------------------------------- positive
def test_pd_tree_contract_clean():
    assert jc.check_round_contract(_pd(), jc.toy_params(K)) == []


def test_pd_kernel_contract_clean():
    opt = _pd(use_kernel=True, kernel_interpret=True)
    assert jc.check_round_contract(opt, jc.toy_params(K), kernel=True) == []


def test_cpd_sign_kernel_contract_clean():
    opt = CPDSGDM(CPDSGDMConfig(eta=0.05, mu=0.9, p=2, gamma=0.4,
                                use_kernel=True, kernel_interpret=True),
                  DenseComm(ring(K)), SignCompressor())
    assert jc.check_round_contract(opt, jc.toy_params(K), kernel=True) == []


def test_scheduled_dense_contract_clean():
    sched = make_schedule("one_peer_exp", (K,))
    opt = PDSGDM(PDSGDMConfig(eta=0.05, mu=0.9, p=2), DenseComm(sched))
    assert jc.check_round_contract(opt, jc.toy_params(K)) == []


def _membership():
    from repro.core.topology import membership_from_events
    return membership_from_events(K, 4, [(1, "kill", 2), (3, "revive", 2),
                                         (2, "straggle", 5)])


def test_membership_contract_clean():
    """Elastic membership on the dense backend: the full round contract
    plus the traced mask semantics (row-stochastic over live peers, e_k
    rows for masked workers, zero dead columns) hold every round."""
    opt = PDSGDM(PDSGDMConfig(eta=0.05, mu=0.9, p=3),
                 DenseComm(ring(K), membership=_membership()))
    assert jc.check_round_contract(opt, jc.toy_params(K)) == []


def test_catches_gossip_with_masked_out_peer():
    """Negative: a backend whose round-r matrix still carries the full
    topology weights (mask never applied) must be flagged — the dense
    trace shows a nonzero column for the dead worker and a non-identity
    row for the masked one."""
    comm = DenseComm(ring(K), membership=_membership())
    # sabotage the precomputed masked tables back to the raw topology W:
    # every round now gossips as if the whole fleet were alive
    comm._Wm = jnp.broadcast_to(jnp.asarray(ring(K).W, jnp.float32),
                                comm._Wm.shape)
    out = jc.check_membership_mask(comm)
    assert out, "unmasked gossip with a dead worker went undetected"
    joined = "\n".join(out)
    assert "masked-out worker" in joined
    # both failure modes surface: the dead worker still mixing, and an
    # active worker reading its column
    assert any("reads weight" in v for v in out)
    assert any("row != e_k" in v for v in out)


def test_membership_mask_check_skips_full_rounds():
    """All-active rounds reuse the topology matrix bitwise — the check
    passes and the traced matrix equals W exactly."""
    from repro.core.topology import full_membership
    comm = DenseComm(ring(K), membership=full_membership(K))
    assert jc.check_membership_mask(comm) == []
    np.testing.assert_array_equal(jc.traced_mixing_matrix(comm, 0),
                                  np.asarray(ring(K).W, np.float32))


def test_qsgd_tree_no_f64():
    """Regression: the qsgd dequant fill literal was a weak f64 scalar
    under x64 (kernels/qsgd_quant.py) — the whole dense round must now
    trace f64-free."""
    from repro.core import QSGDCompressor
    opt = CPDSGDM(CPDSGDMConfig(eta=0.05, mu=0.9, p=2, gamma=0.4),
                  DenseComm(ring(K)), QSGDCompressor())
    jx = jc.trace_round(opt, jc.toy_params(K), 2, x64=True)
    assert jc.check_no_f64(jx) == []


def test_topk_kernel_no_f64():
    """Same regression class for the topk select/scatter kernels."""
    from repro.kernels import topk_select
    rows = topk_select.BLOCK_ROWS
    x = jnp.zeros((rows, 1024), jnp.float32)
    cnt = jnp.full((rows, 1), 1024.0, jnp.float32)
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(
            lambda x, c: topk_select.topk_select_pallas(
                x, c, fraction=0.01, interpret=True))(x, cnt)
    assert jc.check_no_f64(jx) == []


# ------------------------------------------------------------------- negative
def test_catches_callback_in_scan():
    opt = _pd()

    def noisy_grads(params, batch):
        jax.debug.print("step {x}", x=batch.mean())
        return jc.toy_grads_fn(params, batch)

    jx = jc.trace_round(opt, jc.toy_params(K), 3, grads_fn=noisy_grads)
    out = jc.check_no_host_callbacks(jx)
    assert out and "scan depth 1" in out[0]


def test_catches_f64_injection():
    opt = _pd()

    def leaky_grads(params, batch):
        loss, grads = jc.toy_grads_fn(params, batch)
        # a numpy f64 scalar: silently truncated without x64, a genuine
        # f64 operand with it
        grads = jax.tree_util.tree_map(
            lambda g: g * np.float64(1.0), grads)
        return loss, grads

    jx = jc.trace_round(opt, jc.toy_params(K), 3, x64=True,
                        grads_fn=leaky_grads)
    out = jc.check_no_f64(jx)
    assert out and "float64" in out[0]
    # without x64 the leak is invisible — that's why the checker retraces
    jx32 = jc.trace_round(opt, jc.toy_params(K), 3, grads_fn=leaky_grads)
    assert jc.check_no_f64(jx32) == []


def test_catches_wrong_scan_length():
    opt = _pd(p=3)
    jx = jc.trace_round(opt, jc.toy_params(K), 3)
    out = jc.check_round_scan(jx, 5)
    assert out and "p=5" in out[0]


def test_catches_collective_in_dense_round():
    """A dense-backend round that sneaks in a psum is flagged."""
    def bad_round(x):
        return jax.lax.psum(x, "i")

    jx = jax.make_jaxpr(
        lambda x: jax.vmap(bad_round, axis_name="i")(x))(
            jnp.zeros((4, 8), jnp.float32))
    out = jc.check_dense_no_collectives(jx)
    assert out and "psum" in out[0]


def test_catches_missing_schedule_switch():
    sched = make_schedule("one_peer_exp", (K,))     # period 3
    opt = PDSGDM(PDSGDMConfig(eta=0.05, mu=0.9, p=2), DenseComm(sched))
    jx = jc.trace_round(opt, jc.toy_params(K), 2)
    # dense backend indexes stacked W — no lax.switch, so asking for one
    # with period > 2 must fail
    out = jc.check_schedule_switch(jx, 6)
    assert out and "6 branches" in out[0]


def test_kernel_flatten_once_negative():
    """A per-step flatten (tree riding the carry) fails the flatten-once
    check."""
    from repro.kernels import ops as kops
    opt = _pd(p=2)
    params = jc.toy_params(K)
    plan = kops.KernelPlan.for_tree(params, worker_dim=True)
    # tree-form round: the carry holds leaf trees, not the plan matrix
    jx = jc.trace_round(opt, params, 2, kernel=False)
    out = jc.check_kernel_flatten_once(jx, plan, 2)
    assert out and "flatten-once" in out[0]
    # kernel round passes
    jxk = jc.trace_round(opt, params, 2, kernel=True)
    assert jc.check_kernel_flatten_once(jxk, plan, 2) == []


def test_require_raises():
    with pytest.raises(jc.ContractViolation) as ei:
        jc.require(["a", "b"])
    assert ei.value.violations == ["a", "b"]
    jc.require([])   # no-op
