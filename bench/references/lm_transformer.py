"""Plain reference of the decoder-only language model the `olmo-*` configs run.

Written from the configuration alone, in straightforward ``jax.numpy``:
token embedding; per layer a non-parametric LayerNorm (no scale, no bias,
eps 1e-5), multi-head causal attention with rotary embeddings (theta
``rope_theta``, the two halves of each head rotated against each other)
and no biases, a residual add, a second non-parametric LayerNorm, a
SwiGLU MLP (``silu(x W_g) * (x W_i)``, then ``W_o``), a residual add; a
final non-parametric LayerNorm; the output head tied to the embedding
(logits = h Eᵀ), so the table's gradient sums the lookup's and the head's;
mean next-token cross entropy.  Activations, gradients and momentum are float32 and every
matrix product runs at the highest precision; the weights are stored in
the configuration's ``param_dtype`` and rounded to it after each update,
as the configuration states.

``compute`` names the dtype that the operands of every matrix product are
rounded to: ``float32`` for the reference, a lower one for the control.

The weights never leave the device whole: a step runs the layers forward
keeping only each layer's input, then walks them backwards, updating each
layer's weights and momentum in place as soon as its gradient exists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import optim

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def init_leaf(names, shape, key, dtype):
    """The benchmark's weights, leaf by leaf: N(0, 1/d_model) for the
    embedding, which is also the output head (OLMo's own init), and
    N(0, 1/fan_in) for every matrix (fan_in = its second-last dim)."""
    if names[-1] == "table":
        std = shape[-1] ** -0.5
    elif names[-1] == "w":
        std = shape[-2] ** -0.5
    else:
        raise ValueError(f"no initialiser for leaf {'/'.join(names)}")
    return (std * jax.random.normal(key, shape, F32)).astype(dtype)


def check_model(model: dict) -> None:
    want = {"norm": "nonparametric", "gated_mlp": True,
            "tie_embeddings": True, "qkv_bias": False, "window": None}
    for k, v in want.items():
        if model.get(k, v) != v:
            raise ValueError(f"the reference has no {k}={model.get(k)!r}")
    if model["n_kv_heads"] != model["n_heads"]:
        raise ValueError("the reference runs multi-head attention only")


def _ln(h):
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mean), axis=-1, keepdims=True)
    return (h - mean) / jnp.sqrt(var + 1e-5)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope_tables(head_dim: int, seq: int, theta: float):
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim)
    ang = jnp.arange(seq, dtype=F32)[:, None] * inv[None, :]
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]


def _rotate(x, cos, sin):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def make_step(model: dict, opt: dict, compute: str = "float32",
              half_batch: bool = False):
    """One worker's momentum step: ``(x, m, tokens, labels) -> (x, m, loss)``
    with x and m donated.  ``half_batch`` plants a fault: the step sees only
    the first half of its rows and takes the mean over those."""
    check_model(model)
    n_layers, d = model["n_layers"], model["d_model"]
    heads = model["n_heads"]
    hd = d // heads
    cdt = jnp.dtype(compute)
    lr, mu, wd = (float(opt["eta"]), float(opt["mu"]),
                  float(opt["weight_decay"]))

    def lo(a):
        """An operand rounded to the compute dtype, held in float32."""
        return a.astype(F32) if cdt == F32 else a.astype(cdt).astype(F32)

    def mm(a, w):
        return jnp.matmul(lo(a), lo(w), precision=HI)

    def layer(lp, h, cos, sin):
        b, s, _ = h.shape
        x = _ln(h)
        a = lp["attn"]
        q = _rotate(mm(x, a["wq"]["w"]).reshape(b, s, heads, hd), cos, sin)
        k = _rotate(mm(x, a["wk"]["w"]).reshape(b, s, heads, hd), cos, sin)
        v = mm(x, a["wv"]["w"]).reshape(b, s, heads, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", lo(q), lo(k),
                            precision=HI)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores * hd ** -0.5, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", lo(probs), lo(v),
                       precision=HI)
        h = h + mm(o.reshape(b, s, d), a["wo"]["w"])
        f = lp["mlp"]
        x = _ln(h)
        return h + mm(_silu(mm(x, f["wg"]["w"])) * mm(x, f["wi"]["w"]),
                      f["wo"]["w"])

    def head_loss(h, w, labels):
        logits = mm(_ln(h), w)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                             axis=-1))

    def upd(x, m, g):
        return optim.sgdm(x, m, g, lr=lr, mu=mu, wd=wd)

    def pick(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    def put(tree, part, i):
        return jax.tree_util.tree_map(
            lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, i, 0),
            tree, part)

    def to32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)

    def step(x, m, tokens, labels):
        if half_batch:
            tokens, labels = tokens[:tokens.shape[0] // 2], \
                labels[:labels.shape[0] // 2]
        cos, sin = _rope_tables(hd, tokens.shape[1],
                                float(model.get("rope_theta", 10000.0)))
        blocks, mblocks = x["blocks"]["pos0"], m["blocks"]["pos0"]
        h0 = x["embed"]["table"].astype(F32)[tokens]

        def fwd(i, carry):
            h, hs = carry
            hs = jax.lax.dynamic_update_index_in_dim(hs, h, i, 0)
            return layer(to32(pick(blocks, i)), h, cos, sin), hs

        h_last, hs = jax.lax.fori_loop(
            0, n_layers, fwd, (h0, jnp.zeros((n_layers,) + h0.shape, F32)))
        table = x["embed"]["table"]
        loss, head_vjp = jax.vjp(lambda h, w: head_loss(h, w, labels),
                                 h_last, table.astype(F32).T)
        dh, dw_head = head_vjp(jnp.ones((), F32))

        def bwd(j, carry):
            i = n_layers - 1 - j
            dh, xb, mb = carry
            lp, lm = pick(xb, i), pick(mb, i)
            _, vjp = jax.vjp(lambda p, h: layer(p, h, cos, sin), to32(lp),
                             jax.lax.dynamic_index_in_dim(hs, i, 0, False))
            dlp, dh_in = vjp(dh)
            pairs = jax.tree_util.tree_map(upd, lp, lm, dlp)
            outer = jax.tree_util.tree_structure(lp)
            new_lp, new_lm = jax.tree_util.tree_transpose(
                outer, jax.tree_util.tree_structure((0, 0)), pairs)
            return dh_in, put(xb, new_lp, i), put(mb, new_lm, i)

        dh0, blocks, mblocks = jax.lax.fori_loop(
            0, n_layers, bwd, (dh, blocks, mblocks))
        dtable = dw_head.T.at[tokens.reshape(-1)].add(dh0.reshape(-1, d))
        table, mtable = upd(table, m["embed"]["table"], dtable)
        x_new = {**x, "embed": {"table": table}, "blocks": {"pos0": blocks}}
        m_new = {**m, "embed": {"table": mtable},
                 "blocks": {"pos0": mblocks}}
        return x_new, m_new, loss

    return jax.jit(step, donate_argnums=(0, 1))


def run(spec: dict, x0_fn, batch_fn, devices, *, calls: int,
        rounds_per_call: int, variant: dict | None = None) -> dict:
    """Follow the program's first ``calls`` calls of ``rounds_per_call``
    rounds each, one worker per device, state carried across calls.

    ``x0_fn(device)`` gives one worker's starting weights on a device,
    ``batch_fn(rnd)`` a round's batch, leaves (p, K, ...).  ``variant``
    may set ``compute`` (the control), ``half_batch`` or ``topology``
    (faults put in the program's place).  Returns each step's loss (mean
    over workers), the per-worker leaf norms of the momentum after the
    first call, and of the change of the weights after the last.
    """
    variant = variant or {}
    opt, k_workers = spec["optimizer"], spec["workers"]
    p = int(opt["p"])
    w = optim.mixing_matrix(variant.get("topology", spec["topology"]),
                            k_workers)
    step = make_step(spec["model"], opt, variant.get("compute", "float32"),
                     variant.get("half_batch", False))
    dev = [devices[i % len(devices)] for i in range(k_workers)]
    xs = [x0_fn(d) for d in dev]
    # each worker's momentum on its own chip: made on the default chip, the
    # four would not fit there
    ms = [jax.tree_util.tree_map(
        lambda a, d=d: jnp.zeros(a.shape, F32, device=d), x)
        for x, d in zip(xs, dev)]
    # leaf by leaf, so a device holds at most one leaf of each neighbour
    mix = jax.jit(lambda ws, *ls: sum(
        wi * l.astype(F32) for wi, l in zip(ws, ls)).astype(ls[0].dtype))
    norms = jax.jit(optim.leaf_norms)
    losses, m_first = [], None
    for call in range(calls):
        for r in range(rounds_per_call):
            batch = batch_fn(call * rounds_per_call + r)
            for i in range(p):
                step_losses = []
                for kk in range(k_workers):
                    tok = jax.device_put(batch["tokens"][i, kk], dev[kk])
                    lab = jax.device_put(batch["labels"][i, kk], dev[kk])
                    xs[kk], ms[kk], lv = step(xs[kk], ms[kk], tok, lab)
                    step_losses.append(lv)
                losses.append(sum(float(v) for v in step_losses) / k_workers)
            if k_workers > 1:
                leaves = [jax.tree_util.tree_leaves(x) for x in xs]
                treedef = jax.tree_util.tree_structure(xs[0])
                mixed = [[] for _ in range(k_workers)]
                for li in range(len(leaves[0])):
                    for kk in range(k_workers):
                        nbrs = [j for j in range(k_workers) if w[kk, j]]
                        mixed[kk].append(mix(
                            tuple(float(w[kk, j]) for j in nbrs),
                            *[jax.device_put(leaves[j][li], dev[kk])
                              for j in nbrs]))
                del leaves
                xs = [jax.tree_util.tree_unflatten(treedef, ls)
                      for ls in mixed]
        if call == 0:
            m_first = [jax.device_get(norms(m)) for m in ms]
    del ms
    dx = jax.jit(lambda x, x0: optim.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - b.astype(F32), x, x0)))
    dx_norms = [jax.device_get(dx(xs[kk], x0_fn(dev[kk])))
                for kk in range(k_workers)]
    return {"losses": losses, "m_first": m_first, "dx": dx_norms}
