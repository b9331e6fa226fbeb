"""Plain reference of the Mamba-2 / attention hybrid the `granite-4.0-h-*`
configurations run.

Written from the published description alone (the model's ``config.json``,
huggingface.co/ibm-granite/granite-4.0-h-micro, and Mamba-2, arXiv:2405.21060),
in straightforward ``jax.numpy``.  The layers follow the configuration's
``pattern`` of ``(mixer, ffn)`` pairs, repeated ``n_layers / len(pattern)``
times.  Each layer:

* a pre-norm, RMSNorm with a scale (eps ``norm_eps``), then the mixer:

  - ``mamba`` (Mamba-2): ``in_proj`` (no bias) to z, xBC and dt; a causal
    depthwise conv of xBC (kernel ``conv_w``, bias ``conv_b``) and SiLU;
    x, B, C split from it (one group, so B and C are shared by every head);
    Δ_t = softplus(dt_t + dt_bias), A = −exp(A_log), and the recurrence,
    step by step::

        h_t = exp(Δ_t A) h_{t−1} + Δ_t x_t B_tᵀ,   y_t = h_t C_t + D x_t

    (per head, h_t of head_dim × d_state); then the gated norm
    ``rmsnorm(y · silu(z))`` over the whole inner width, and ``out_proj``;
  - ``attn``: grouped-query attention with no positional embedding (NoPE)
    and no biases, softmax scale ``attn_scale``, causal;

* the residual add of the mixer's output times ``residual_scale``;
* a second pre-norm and the SwiGLU MLP (``silu(x W_g) * (x W_i)``, then
  ``W_o``), its output times ``residual_scale`` added to the residual.

The token embedding is multiplied by ``embed_scale``; after a final RMSNorm
the output head is tied to the embedding and the logits are divided by
``logits_scale``; the loss is the mean next-token cross entropy.
Activations, gradients and momentum are float32, and every matrix product
runs at the highest precision; the weights are stored in the dtypes of the
program's tree (the configuration's ``param_dtype``; the Mamba-2
``A_log``, ``dt_bias`` and ``D`` in float32) and rounded to them after each
update, as the configuration states.

Departures from the published model, none of them in the mathematics:

* random weights from ``init_leaf``, not the trained ones;
* the recurrence is checkpointed every ``SEGMENT`` steps, the attention
  computed in blocks of ``Q_BLOCK`` queries and the head in blocks of
  ``HEAD_BLOCK`` positions, so that the gradient fits at seq 4096;
* the weights are held in the program's tree (``blocks/pos<i>`` stacked
  over the pattern's repeats) so that the two can be compared leaf by leaf.

``compute`` names the dtype that the operands of every matrix product
(and of the recurrence's products) are rounded to: ``float32`` for the
reference, a lower one for the control.

A step runs each layer as a call of its own: forward keeping only each
layer's input, then the layers backwards, each layer's weights and
momentum updated in place as soon as its gradient exists.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import optim

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SEGMENT = 256         # recurrence steps between checkpoints
Q_BLOCK = 512         # queries per attention block
HEAD_BLOCK = 512      # positions per block of the output head
CONV_KERNEL = 4       # the published conv width (``mamba_d_conv``)


def init_leaf(names, shape, key, dtype):
    """The benchmark's weights, leaf by leaf: N(0, 1/d_model) for the
    embedding, which is also the output head; N(0, 1/fan_in) for every
    matrix and for the conv kernel (fan_in = its second-last dim); the
    conv bias U(±1/√kernel), PyTorch's own for a depthwise conv; norm
    scales 1; and Mamba-2's published kinds: A = exp(A_log) uniform in
    [1, 16], dt_bias the inverse softplus of a dt log-uniform in
    [0.001, 0.1], D = 1."""
    leaf = names[-1]
    if leaf == "table":
        val = shape[-1] ** -0.5 * jax.random.normal(key, shape, F32)
    elif leaf in ("w", "conv_w"):
        val = shape[-2] ** -0.5 * jax.random.normal(key, shape, F32)
    elif leaf == "conv_b":
        bound = CONV_KERNEL ** -0.5
        val = jax.random.uniform(key, shape, F32, -bound, bound)
    elif leaf in ("scale", "D"):
        val = jnp.ones(shape, F32)
    elif leaf == "A_log":
        val = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif leaf == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        val = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"no initialiser for leaf {'/'.join(map(str, names))}")
    # rounded to the stored dtype by an op the compiler keeps: on a TPU a
    # bare convert pair f32 -> bf16 -> f32 fused into another program keeps
    # f32 precision, and the weights made again there would differ from
    # those stored by the rounding of every element
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(val, info.nexp, info.nmant).astype(dtype)


def pattern(model: dict) -> list:
    """The configuration's pattern as (mixer, ffn) pairs."""
    return [tuple(p) for p in model["pattern"]]


def check_model(model: dict) -> None:
    want = {"norm": "rmsnorm", "gated_mlp": True, "tie_embeddings": True,
            "qkv_bias": False, "window": None, "attn_positions": "none"}
    for k, v in want.items():
        if model.get(k, v) != v:
            raise ValueError(f"the reference has no {k}={model.get(k)!r}")
    for mixer, ffn in pattern(model):
        if mixer not in ("mamba", "attn") or ffn != "dense":
            raise ValueError(f"the reference has no layer ({mixer}, {ffn})")
    if model["n_layers"] % len(pattern(model)):
        raise ValueError("n_layers is not a whole number of periods")


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def make_layers(model: dict, compute: str = "float32",
                half_batch: bool = False) -> dict:
    """The model's pieces on float32 weights: ``ssm(mp, x)`` (the Mamba-2
    mixer alone, on normed input), ``layer(mixer, lp, h)`` (one whole
    layer) and ``head_loss(h, scale, w, labels)`` (final norm, head and
    loss; ``w`` is the table transposed).  ``half_batch`` plants a fault:
    the loss leaves out half of the step's tokens (the second half of the
    rows, or with a single row the second half of its positions) and takes
    the mean over the rest."""
    check_model(model)
    d, heads, kv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // heads
    n_state, p_head = model["ssm_state"], model["ssm_headdim"]
    d_inner = model["ssm_expand"] * d
    ssm_heads = d_inner // p_head
    eps = float(model["norm_eps"])
    attn_scale = float(model["attn_scale"])
    rs, ls = float(model["residual_scale"]), float(model["logits_scale"])
    cdt = jnp.dtype(compute)

    def lo(a):
        """An operand rounded to the compute dtype, held in float32."""
        return a.astype(F32) if cdt == F32 else a.astype(cdt).astype(F32)

    def mm(a, w):
        return jnp.matmul(lo(a), lo(w), precision=HI)

    def rms(h, scale):
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        return h / jnp.sqrt(var + eps) * scale

    def recurrence(xs, B, C, dt, A):
        """y_t = h_t C_t with h_t = exp(Δ_t A) h_{t−1} + Δ_t x_t B_tᵀ, step
        by step from h_0 = 0, checkpointed every ``SEGMENT`` steps.
        xs (b, s, H, P); B, C (b, s, N); dt (b, s, H)."""
        b, s = xs.shape[:2]
        seg = math.gcd(s, SEGMENT)
        decay = jnp.exp(dt * A)

        def steps(a):       # (b, s, ...) -> (s/seg, seg, b, ...)
            a = jnp.moveaxis(a, 1, 0)
            return a.reshape((s // seg, seg) + a.shape[1:])

        def one(h, inp):
            x_t, b_t, c_t, dt_t, dec_t = inp
            h = (dec_t[:, :, None, None] * h
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
            return h, jnp.einsum("bhpn,bn->bhp", lo(h), c_t, precision=HI)

        @jax.checkpoint
        def segment(h, inp):
            return jax.lax.scan(one, h, inp)

        h0 = jnp.zeros((b, ssm_heads, p_head, n_state), F32)
        _, ys = jax.lax.scan(segment, h0, tuple(
            steps(a) for a in (lo(xs), lo(B), lo(C), dt, decay)))
        return jnp.moveaxis(ys.reshape((s,) + ys.shape[2:]), 0, 1)

    def ssm(mp, x):
        b, s, _ = x.shape
        zxbcdt = mm(x, mp["in_proj"]["w"])
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * n_state],
                               axis=-1)
        w, k = mp["conv_w"], mp["conv_w"].shape[0]
        pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        xbc = _silu(sum(pad[:, i:i + s] * w[i] for i in range(k))
                    + mp["conv_b"])
        xs, B, C = jnp.split(xbc, [d_inner, d_inner + n_state], axis=-1)
        xs = xs.reshape(b, s, ssm_heads, p_head)
        dt = _softplus(dt + mp["dt_bias"])
        y = recurrence(xs, B, C, dt, -jnp.exp(mp["A_log"]))
        y = (y + mp["D"][:, None] * xs).reshape(b, s, d_inner)
        y = rms(y * _silu(z), mp["norm"]["scale"])
        return mm(y, mp["out_proj"]["w"])

    def mamba(lp, h):
        return h + rs * ssm(lp["mamba"], rms(h, lp["norm_mix"]["scale"]))

    def attention(lp, h):
        b, s, _ = h.shape
        a = lp["attn"]
        x = rms(h, lp["norm_mix"]["scale"])
        q = mm(x, a["wq"]["w"]).reshape(b, s, kv, heads // kv, hd)
        k = mm(x, a["wk"]["w"]).reshape(b, s, kv, hd)
        v = mm(x, a["wv"]["w"]).reshape(b, s, kv, hd)
        qb = math.gcd(s, Q_BLOCK)

        @jax.checkpoint
        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
            scores = jnp.einsum("bqkgd,bskd->bkgqs", lo(qi), lo(k),
                                precision=HI) * attn_scale
            causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("bkgqs,bskd->bqkgd", lo(probs), lo(v),
                              precision=HI)

        o = jax.lax.map(block, jnp.arange(s // qb))     # (nb, b, qb, kv, g, hd)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, heads * hd)
        return h + rs * mm(o, a["wo"]["w"])

    def layer(mixer, lp, h):
        h = mamba(lp, h) if mixer == "mamba" else attention(lp, h)
        f = lp["mlp"]
        x = rms(h, lp["norm_ffn"]["scale"])
        return h + rs * mm(_silu(mm(x, f["wg"]["w"])) * mm(x, f["wi"]["w"]),
                           f["wo"]["w"])

    def weights(shape):
        """(b, s) weights of the tokens the loss counts."""
        b, s = shape
        if not half_batch:
            return jnp.ones(shape, F32)
        if b >= 2:
            return (jnp.arange(b)[:, None] < b // 2).astype(F32) * \
                jnp.ones(shape, F32)
        return (jnp.arange(s)[None, :] < s // 2).astype(F32)

    def head_loss(h, scale, w, labels):
        b, s, _ = h.shape
        cs = math.gcd(s, HEAD_BLOCK)
        x = rms(h, scale)
        wt = weights((b, s))

        @jax.checkpoint
        def block(i):
            xi = jax.lax.dynamic_slice_in_dim(x, i * cs, cs, axis=1)
            li = jax.lax.dynamic_slice_in_dim(labels, i * cs, cs, axis=1)
            wi = jax.lax.dynamic_slice_in_dim(wt, i * cs, cs, axis=1)
            logp = jax.nn.log_softmax(mm(xi, w) / ls, axis=-1)
            nll = -jnp.take_along_axis(logp, li[..., None], axis=-1)[..., 0]
            return jnp.sum(nll * wi)
        return jnp.sum(jax.lax.map(block, jnp.arange(s // cs))) / jnp.sum(wt)

    return {"ssm": ssm, "layer": layer, "head_loss": head_loss}


def make_step(model: dict, opt: dict, compute: str = "float32",
              half_batch: bool = False):
    """One worker's momentum step: ``(x, m, tokens, labels) -> (x, m, loss)``,
    x and m updated in place; ``compute`` and ``half_batch`` as in
    ``make_layers``."""
    parts = make_layers(model, compute, half_batch)
    layer, head_loss = parts["layer"], parts["head_loss"]
    layers = pattern(model)
    repeats = model["n_layers"] // len(layers)
    d, es = model["d_model"], float(model["embed_scale"])
    lr, mu, wd = (float(opt["eta"]), float(opt["mu"]),
                  float(opt["weight_decay"]))

    def upd(x, m, g):
        return optim.sgdm(x, m, g, lr=lr, mu=mu, wd=wd)

    def update(tree, mtree, grads):
        pairs = jax.tree_util.tree_map(upd, tree, mtree, grads)
        return jax.tree_util.tree_transpose(
            jax.tree_util.tree_structure(tree),
            jax.tree_util.tree_structure((0, 0)), pairs)

    def pick(tree, r):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False),
            tree)

    def put(tree, part, r):
        return jax.tree_util.tree_map(
            lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, r, 0),
            tree, part)

    def to32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)

    embed = jax.jit(lambda table, tokens: es * table[tokens].astype(F32))
    forward = {mixer: jax.jit(lambda blk, r, h, mixer=mixer: layer(
        mixer, to32(pick(blk, r)), h)) for mixer in ("mamba", "attn")}

    def backward_fn(mixer, blk, mblk, r, h, dh):
        lp = pick(blk, r)
        _, vjp = jax.vjp(lambda p, hh: layer(mixer, p, hh), to32(lp), h)
        dlp, dh_in = vjp(dh)
        new_lp, new_lm = update(lp, pick(mblk, r), dlp)
        return put(blk, new_lp, r), put(mblk, new_lm, r), dh_in

    backward = {mixer: jax.jit(
        lambda blk, mblk, r, h, dh, mixer=mixer: backward_fn(
            mixer, blk, mblk, r, h, dh), donate_argnums=(0, 1))
        for mixer in ("mamba", "attn")}

    @jax.jit
    def head(h, scale, table, labels):
        loss, vjp = jax.vjp(lambda hh, sc, w: head_loss(hh, sc, w, labels),
                            h, scale.astype(F32), table.astype(F32).T)
        dh, dscale, dw = vjp(jnp.ones((), F32))
        return loss, dh, dscale, dw

    norm_update = jax.jit(upd, donate_argnums=(0, 1))

    def table_update(table, mtable, dw_head, dh0, tokens):
        grad = dw_head.T.at[tokens.reshape(-1)].add(es * dh0.reshape(-1, d))
        return upd(table, mtable, grad)
    table_update = jax.jit(table_update, donate_argnums=(0, 1))

    order = [(r, pos) for r in range(repeats) for pos in range(len(layers))]

    def step(x, m, tokens, labels):
        blocks, mblocks = dict(x["blocks"]), dict(m["blocks"])
        h = embed(x["embed"]["table"], tokens)
        inputs = []
        for r, pos in order:
            inputs.append(h)
            h = forward[layers[pos][0]](blocks[f"pos{pos}"], r, h)
        loss, dh, dscale, dw = head(h, x["final_norm"]["scale"],
                                    x["embed"]["table"], labels)
        fscale, mfscale = norm_update(x["final_norm"]["scale"],
                                      m["final_norm"]["scale"], dscale)
        for (r, pos), h_in in reversed(list(zip(order, inputs))):
            key = f"pos{pos}"
            blocks[key], mblocks[key], dh = backward[layers[pos][0]](
                blocks[key], mblocks[key], r, h_in, dh)
        del inputs
        table, mtable = table_update(x["embed"]["table"], m["embed"]["table"],
                                     dw, dh, tokens)
        x_new = {**x, "embed": {"table": table}, "blocks": blocks,
                 "final_norm": {"scale": fscale}}
        m_new = {**m, "embed": {"table": mtable}, "blocks": mblocks,
                 "final_norm": {"scale": mfscale}}
        return x_new, m_new, loss

    return step


def run(spec: dict, x0_fn, batch_fn, devices, *, calls: int,
        rounds_per_call: int, variant: dict | None = None) -> dict:
    """Follow the program's first ``calls`` calls of ``rounds_per_call``
    rounds each, one worker per device, state carried across calls; the
    interface and the readings of ``lm_transformer.run``.

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
    with jax.default_matmul_precision("highest"):
        step = make_step(spec["model"], opt,
                         variant.get("compute", "float32"),
                         variant.get("half_batch", False))
        dev = [devices[i % len(devices)] for i in range(k_workers)]
        xs = [x0_fn(d) for d in dev]
        ms = [jax.tree_util.tree_map(
            lambda a, d=d: jnp.zeros(a.shape, F32, device=d), x)
            for x, d in zip(xs, dev)]
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
                    losses.append(sum(float(v) for v in step_losses)
                                  / k_workers)
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
