"""The plain reference: the architecture's equations in straightforward
``jax.numpy`` and float32, with no kernels, no cache and no batching.

It imports nothing of the program and takes nothing the program has made: its
weights come from ``weights.py`` and the seed. Matrix products run at
``highest`` precision (on a TPU a float32 product otherwise runs in bfloat16
passes). It works a sequence at a time and a layer at a time so that it fits
beside nothing else on one chip.

Equations (decoder-only transformer as the Mistral and SmolLM2 reference
implementations state them): x += Attn(RMSNorm(x)); x += SwiGLU(RMSNorm(x));
rotary embedding on interleaved pairs (the convention of the models' own
reference code; the Hugging Face port permutes the projection columns to use
half-split pairs instead, which with seeded random weights is the same
model); grouped-query causal attention with softmax in float32; the loss is
the mean cross entropy over every position of the batch.

``precision="fp8"`` is the control of "How correct is decided": the same
equations with both operands of every matrix product rounded to float8
(e4m3, one scale per tensor), the nearest precision below the bfloat16 that
both configurations state. It has to come out as not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .costs import head_dim

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _fp8_round(x):
    """Round to float8 e4m3 with one scale per tensor; gradients pass
    straight through, as quantized training does."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _einsum(precision, spec, a, b):
    if precision == "fp8":
        a, b = _fp8_round(a), _fp8_round(b)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=F32)


def rope_tables(seq: int, d: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rope(x, cos, sin):
    """x [S, H, D]: rotate pairs (2i, 2i+1) by position * theta^(-2i/D)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_forward(x, w, cos, sin, cfg, precision="f32"):
    """One decoder layer over one sequence. x [S, hidden] float32; ``w`` the
    layer's leaves in float32."""
    es = functools.partial(_einsum, precision)
    s = x.shape[0]
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    a = _rms(x, w["ln1"], eps)
    q = _rope(es("se,ef->sf", a, w["wq"]).reshape(s, h, d), cos, sin)
    k = _rope(es("se,ef->sf", a, w["wk"]).reshape(s, kv, d), cos, sin)
    v = es("se,ef->sf", a, w["wv"]).reshape(s, kv, d)
    g = h // kv
    qg = q.reshape(s, kv, g, d)
    scores = es("qkgd,tkd->kgqt", qg, k) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    ctx = es("kgqt,tkd->qkgd", probs, v).reshape(s, h * d)
    x = x + es("sf,fe->se", ctx, w["wo"])
    b = _rms(x, w["ln2"], eps)
    mlp = jax.nn.silu(es("se,ef->sf", b, w["w_gate"])) \
        * es("se,ef->sf", b, w["w_up"])
    return x + es("sf,fe->se", mlp, w["w_down"])


def head_logits(x, top, cfg, precision="f32"):
    """Final norm and output head over rows x [N, hidden]."""
    x = _rms(x, top["norm"], cfg["rms_norm_eps"])
    if cfg.get("tie_word_embeddings"):
        return _einsum(precision, "ne,ve->nv", x, top["embed"])
    return _einsum(precision, "ne,ev->nv", x, top["lm_head"])


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


# -- serving: logits of the served positions ---------------------------------

def served_logits(cfg, seed: int, ids: np.ndarray, rows: list,
                  precision="f32"):
    """Full forward over right-padded sequences ``ids`` [N, T], one layer at
    a time (a layer's float32 weights are made, used for every sequence and
    dropped). ``rows[n]`` lists the positions of sequence n whose next-token
    logits are wanted. Returns a list of [len(rows[n]), vocab] arrays
    (numpy, float32)."""
    n, t = ids.shape
    cos, sin = rope_tables(t, head_dim(cfg), cfg["rope_theta"])
    top = _f32(W.make_top(cfg, seed))
    hidden = top["embed"][jnp.asarray(ids)]                  # [N, T, E]

    # weights and tables are arguments: a closed-over array would be baked
    # into the program as a constant and folded on the host
    @jax.jit
    def run_layer(hidden, w, cos, sin):
        w = _f32(w)
        return jax.lax.map(
            lambda x: layer_forward(x, w, cos, sin, cfg, precision), hidden)

    for i in range(cfg["num_hidden_layers"]):
        hidden = run_layer(hidden, W.make_layer(cfg, seed, i), cos, sin)
    head = jax.jit(lambda x, top: head_logits(x, top, cfg, precision))
    longest = max(len(r) for r in rows)
    out = []
    for i, r in enumerate(rows):           # one shape: rows padded by repeat
        idx = np.asarray(list(r) + [r[-1]] * (longest - len(r)))
        out.append(np.asarray(head(hidden[i, jnp.asarray(idx)],
                                   top))[:len(r)])
    return out


# -- training: loss, gradient and AdamW over the first steps -----------------

def init_params(cfg, seed: int) -> dict:
    """The training state's leaves in float32 (the rounded bfloat16 values,
    as the optimizer's master copy starts from them): stacked layers plus
    embedding and final norm (and the head where untied)."""
    p = dict(_f32(W.make_stack(cfg, seed)))
    p.update(_f32(W.make_top(cfg, seed)))
    return p


def _row_loss(params, ids, labels, cos, sin, cfg, precision, denom):
    layers = {k: params[k] for k in W.LAYER_LEAVES}
    top = {k: v for k, v in params.items() if k not in W.LAYER_LEAVES}

    @jax.checkpoint
    def body(x, w):
        return layer_forward(x, w, cos, sin, cfg, precision), None

    x, _ = jax.lax.scan(body, params["embed"][ids], layers)
    logits = head_logits(x, top, cfg, precision)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], 1)) / denom


@functools.lru_cache(maxsize=None)
def _row_grad(cfg_items, precision, denom):
    """One compiled loss-and-gradient of a row for each configuration and
    precision, whatever the step."""
    cfg = dict(cfg_items)
    return jax.jit(jax.value_and_grad(
        lambda p, i, l, cos, sin: _row_loss(p, i, l, cos, sin, cfg,
                                            precision, denom)))


def loss_and_grads(params, ids, labels, cfg, precision="f32"):
    """Mean cross entropy over the whole batch and its gradient, a row at a
    time. ids, labels: [B, S] int."""
    b, s = ids.shape
    cos, sin = rope_tables(s, head_dim(cfg), cfg["rope_theta"])
    fn = _row_grad(tuple(sorted((k, v) for k, v in cfg.items()
                                if isinstance(v, (int, float, bool)))),
                   precision, float(b * s))
    loss, grads = 0.0, None
    for r in range(b):
        l_r, g_r = fn(params, jnp.asarray(ids[r]), jnp.asarray(labels[r]),
                      cos, sin)
        loss = loss + l_r
        grads = g_r if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g_r)
    return loss, grads


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, step, lr, b1, b2, eps, decay):
    """AdamW with decoupled decay and bias correction (Loshchilov & Hutter),
    the update the configuration names."""
    def one(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        mhat = m2 / (1 - b1 ** step)
        vhat = v2 / (1 - b2 ** step)
        return p * (1 - lr * decay) - lr * mhat / (jnp.sqrt(vhat) + eps), \
            m2, v2
    out = {k: one(params[k], grads[k], m[k], v[k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def leaf_norms(tree) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))))
            for k, a in tree.items()}


def train_steps(cfg, seed: int, batches, opt: dict, precision="f32"):
    """Follow the first steps. Returns the loss of each step, the leaf norms
    of the first gradient and the leaf norms of the parameters' change after
    the last step."""
    params = init_params(cfg, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for n, (ids, labels) in enumerate(batches, 1):
        loss, grads = loss_and_grads(params, ids, labels, cfg, precision)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        params, m, v = _adamw(params, grads, m, v, float(n),
                              opt["learning_rate"], opt["beta1"],
                              opt["beta2"], opt["epsilon"],
                              opt["weight_decay"])
        del grads
    start = init_params(cfg, seed)
    delta = leaf_norms({k: params[k] - start[k] for k in params})
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def norm_gap(prog: dict, ref: dict):
    """Worst leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for k, r in ref.items():
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return worst, where
