"""The plain reference: a family's equations in straightforward
``jax.numpy`` and float32, with no kernels, no cache and no batching.

It imports nothing of the program and takes nothing the program has made: its
weights come from ``weights.py`` and the seed, its equations from the
configuration's family (``chipbench/families/``), whose every matrix product
goes through ``einsum`` below at ``highest`` precision (on a TPU a float32
product otherwise runs in bfloat16 passes). Kept here is what is the same
for every architecture: the walk over sequences and layers (a sequence at a
time and a layer at a time, so that it fits beside nothing else on one
chip), the loss (the mean cross entropy over every position of the batch),
its gradient, AdamW, and the measure of a gap between norms.

``precision="fp8"`` is the control of "How correct is decided": the same
equations with both operands of every matrix product rounded to float8
(e4m3, one scale per tensor), the nearest precision below the bfloat16 that
the configurations state. It has to come out as not correct.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import families
from . import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _fp8_round(x):
    """Round to float8 e4m3 with one scale per tensor; gradients pass
    straight through, as quantized training does."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def einsum(precision, spec, a, b):
    """The one matrix product of every family's equations."""
    if precision == "fp8":
        a, b = _fp8_round(a), _fp8_round(b)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=F32)


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
    family = families.of(cfg)
    n, t = ids.shape
    tables = family.position_tables(t, cfg)
    top = _f32(W.make_top(cfg, seed))
    hidden = family.embed_tokens(jnp.asarray(ids), top, cfg)   # [N, T, E]

    # weights and tables are arguments: a closed-over array would be baked
    # into the program as a constant and folded on the host. One program a
    # kind of layer: ``layer`` is the first layer of its kind
    @functools.partial(jax.jit, static_argnums=(3,))
    def run_layer(hidden, w, tables, layer):
        w = _f32(w)
        return jax.lax.map(
            lambda x: family.layer_forward(x, w, tables, cfg, layer,
                                           precision), hidden)

    kinds = families.layer_kinds(family, cfg)
    for i, kind in enumerate(kinds):
        hidden = run_layer(hidden, W.make_layer(cfg, seed, i), tables,
                           kinds.index(kind))
    head = jax.jit(lambda x, top: family.head_logits(x, top, cfg, precision))
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
    the leaves outside them."""
    p = dict(_f32(W.make_stack(cfg, seed)))
    p.update(_f32(W.make_top(cfg, seed)))
    return p


def _row_loss(family, params, ids, labels, tables, cfg, precision, denom):
    leaves = set(family.layer_shapes(cfg, 0))
    layers = {k: v for k, v in params.items() if k in leaves}
    top = {k: v for k, v in params.items() if k not in leaves}

    @jax.checkpoint
    def body(x, w):                # init_params stacked layers of one kind
        return family.layer_forward(x, w, tables, cfg, 0, precision), None

    x, _ = jax.lax.scan(body, family.embed_tokens(ids, top, cfg), layers)
    logits = family.head_logits(x, top, cfg, precision)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], 1)) / denom


@functools.lru_cache(maxsize=None)
def _row_grad(cfg_json, precision, denom):
    """One compiled loss-and-gradient of a row for each configuration and
    precision, whatever the step."""
    cfg = json.loads(cfg_json)
    family = families.of(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, i, l, tables: _row_loss(family, p, i, l, tables, cfg,
                                          precision, denom)))


def loss_and_grads(params, ids, labels, cfg, precision="f32"):
    """Mean cross entropy over the whole batch and its gradient, a row at a
    time. ids, labels: [B, S] int."""
    b, s = ids.shape
    tables = families.of(cfg).position_tables(s, cfg)
    fn = _row_grad(json.dumps(cfg, sort_keys=True), precision, float(b * s))
    loss, grads = 0.0, None
    for r in range(b):
        l_r, g_r = fn(params, jnp.asarray(ids[r]), jnp.asarray(labels[r]),
                      tables)
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
