"""The fused round's named scopes reach the compiled HLO.

A device trace splits a round by the ``jax.named_scope``s that
``PDSGDM.round`` / ``kernel_round`` and the transformer's attention
sub-layers set: they live in the optimized HLO's
``metadata={op_name=...}``.  Each case compiles a small
round on the CPU and looks for every scope as a path segment of some
op_name, once autodiff's ``jvp(...)`` / ``transpose(...)`` wrappers are
taken off, so a refactor that drops one fails here.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_smoke_config
from repro.core import CPDSGDM, CPDSGDMConfig, PDSGDM, PDSGDMConfig
from repro.core import SignCompressor
from repro.core import pdsgdm
from repro.core.gossip import DenseComm
from repro.core.topology import ring
from repro.models import transformer
from repro.models.transformer import make_model

K, P, B, S = 2, 2, 1, 16

CFG = get_smoke_config("olmo-1b").model
ROUND = (pdsgdm.SCOPE_GRAD, pdsgdm.SCOPE_LOCAL_STEP, pdsgdm.SCOPE_GOSSIP)
# the attention mixers the model runs, each scoped by its kind
ATTENTION = tuple(sorted({spec.mixer for spec in CFG.pattern}
                         & set(transformer.ATTENTION_SCOPES)))


def _segments(text: str) -> set:
    out = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for seg in op_name.split("/"):
            while (m := re.match(r"^[A-Za-z_]\w*\((.*)\)$", seg)):
                seg = m.group(1)
            out.add(seg)
    return out


def _optimizer(path: str):
    comm = DenseComm(ring(K))
    if path == "cpd":
        return CPDSGDM(CPDSGDMConfig(eta=0.05, mu=0.9, p=P, gamma=0.4),
                       comm, SignCompressor())
    return PDSGDM(PDSGDMConfig(eta=0.05, mu=0.9, p=P,
                               overlap=path == "overlap",
                               use_kernel=path == "kernel",
                               kernel_interpret=True), comm)


@functools.lru_cache(maxsize=None)
def _compiled_segments(path: str) -> frozenset:
    model = make_model(CFG)
    params = jax.eval_shape(jax.vmap(lambda k: model.init(k)),
                            jax.random.split(jax.random.PRNGKey(0), K))
    opt = _optimizer(path)
    state = jax.eval_shape(opt.init, params)
    tok = jax.ShapeDtypeStruct((P, K, B, S), jnp.int32)
    batches = {"tokens": tok, "labels": tok}
    grad = jax.vmap(jax.value_and_grad(
        lambda p, b: model.loss(p, b, remat="full")[0]))

    def grads_fn(p, b):
        losses, grads = grad(p, b)
        return losses.mean(), grads

    def rnd(state, params, batches):
        return opt.round(state, params, grads_fn, batches)
    return frozenset(_segments(
        jax.jit(rnd).lower(state, params, batches).compile().as_text()))


@pytest.mark.parametrize("scope", ROUND + ATTENTION)
@pytest.mark.parametrize("path", ("tree", "overlap", "kernel", "cpd"))
def test_the_round_scopes_reach_the_compiled_hlo(path, scope):
    assert scope in _compiled_segments(path)


@functools.lru_cache(maxsize=None)
def _model_segments(arch: str):
    cfg = get_smoke_config(arch).model
    model = make_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    grad = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))
    text = grad.lower(params, {"tokens": tok,
                               "labels": tok}).compile().as_text()
    return (_segments(text), {spec.mixer for spec in cfg.pattern},
            re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("arch", ("minicpm3-4b", "jamba-1.5-large-398b",
                                  "mamba2-1.3b", "granite-4.0-h-micro"))
def test_each_attention_kind_is_scoped_by_its_mixer(arch):
    segs, mixers, op_names = _model_segments(arch)
    for kind in transformer.ATTENTION_SCOPES:
        assert (kind in segs) == (kind in mixers), kind
    # the Mamba-2 mixer has a scope of its own, on no op of an attention
    # sub-layer
    assert (transformer.SSM_SCOPE in segs) == ("mamba" in mixers)
    assert "mamba" not in segs
    attention = set(transformer.ATTENTION_SCOPES)
    for op_name in op_names:
        op_segs = _segments(f'op_name="{op_name}"')
        assert not (transformer.SSM_SCOPE in op_segs and op_segs & attention)
