"""Family ``sambay``: the hybrid decoder of Phi-4-mini-flash-reasoning
(``model_type: phi4flash``; arXiv:2507.06607, "SambaY"): state-space, window
attention, one full-attention layer, and a cross-decoder of gated memory
units and cross attention that read what layers L/2 and L/2 + 1 kept.

Equations, for L layers (L divisible by 4), hidden d, heads of h = d / H:

* block l: ``x += Mix_l(LN(x)); x += MLP(LN'(x))``; LN is LayerNorm with gain
  and bias; ``MLP(u) = (silu(g) * p) W2`` with ``[g, p] = u W1``.
* kind of ``Mix_l``: l < L/2: even state-space, odd window attention;
  l = L/2: state-space, whose scan output is kept as the memory M;
  l = L/2 + 1: full attention, whose K and V are kept; l >= L/2 + 2: even a
  gated memory unit over M, odd cross attention to the kept K, V.
* state-space (Mamba-1): ``[a, z] = u W_in``; ``a = silu(conv4(a) + b_c)``
  (causal, depthwise); ``[r, B, C] = a W_x``; ``dt = softplus(r W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t A) * h_(t-1) + (dt_t a_t)
  (x) B_t``; ``y_t = h_t C_t + D * a_t``; output ``(y * silu(z)) W_out``.
* differential attention: query heads pair as (2p, 2p+1), key heads as
  (2g, 2g+1) with g = p // 2, ``V_g = concat(v_2g, v_2g+1)``;
  ``A1 = softmax(q_2p k_2g^T / sqrt(h) + mask) V_g``, ``A2`` likewise from
  the odd heads; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``,
  ``lam0(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_p = (1 - lam0) RMSNorm(A1 - lam
  A2; gain)``; output ``concat_p(o_p) W_o + b_o``. Causal; a window layer
  adds ``i - j < sliding_window`` (the window counts the current token).
  No rotary or other positional term anywhere.
* gated memory unit: ``(silu(u W_1) * M) W_2``.
* head: final LayerNorm, ``logits = x E^T`` with the embedding E (tied).

``reference.served_logits`` hands a layer only ``hidden``, so M and the kept
K, V ride as extra columns of ``hidden`` from layer L/2 on; ``lam0`` needs
the true layer index, so every attention layer is a kind of its own.

Nothing of the program is imported here but inside ``program_model``. The
count functions at the end are the numerators of this family's per-layer
metrics: what the equations need, whatever implements them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..reference import F32, einsum

SPANS = ()        # the host never touches per-slot state at release
SCOPES = ("norm", "ssm", "in_proj", "conv", "ssm_scan", "ssm_step",
          "out_proj", "window_attention", "kv_write", "full_attention",
          "cross_attention", "gmu")
_ROUTES = ("kernel", "gather")
COUNTERS = (
    ("state_bytes", "serving.recurrent_state_bytes", {}),
    ("state_resets", "serving.state_resets", {}),
    ("window_rows_overwritten", "serving.window_rows_overwritten", {}),
    ("window_rows_read", "serving.window_rows_read", {}),
) + tuple(
    (f"decode_launches_{r}", "serving_decode_attention_launches_total",
     {"engine": "paged",
      "path": f"window={r},full={r},cross={r}"}) for r in _ROUTES)


# -- sizes --------------------------------------------------------------------

def sizes(cfg) -> dict:
    """Every size of the architecture from the configuration's keys; the
    state-space sizes are the family's defaults where the file has none."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rank = cfg.get("mamba_dt_rank", "auto")
    return {
        "d": d, "heads": heads, "kv": cfg["num_key_value_heads"],
        "h": d // heads, "ffn": cfg["intermediate_size"],
        "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
        "window": cfg["sliding_window"],
        "di": cfg.get("mamba_expand", 2) * d,
        "n": cfg.get("mamba_d_state", 16),
        "conv": cfg.get("mamba_d_conv", 4),
        "rank": math.ceil(d / 16) if rank == "auto" else rank}


def mixer_kind(cfg, layer: int) -> str:
    half = cfg["num_hidden_layers"] // 2
    if layer < half:
        return "window" if layer % 2 else "ssm"
    if layer == half:
        return "ssm_mem"
    if layer == half + 1:
        return "full"
    return "cross" if layer % 2 else "gmu"


def lam0(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# -- 1. the program's model ---------------------------------------------------

def program_model(cfg: dict, **extra):
    from paddle_tpu.models.sambay import SambaYConfig, SambaYForCausalLM
    s = sizes(cfg)
    return SambaYForCausalLM(SambaYConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_eps=cfg["layer_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        initializer_range=cfg["initializer_range"],
        mamba_d_state=s["n"], mamba_d_conv=s["conv"],
        mamba_expand=s["di"] // s["d"], mamba_dt_rank=s["rank"], **extra))


# -- 2. the leaves ------------------------------------------------------------

def layer_kind(cfg, layer: int):
    """Attention layers are each a kind of their own (``lam0`` needs the
    layer's index, and ``layer_forward`` is told the first of a kind)."""
    kind = mixer_kind(cfg, layer)
    return (kind, layer) if kind in ("window", "full", "cross") else kind


def layer_shapes(cfg, layer: int) -> dict:
    s = sizes(cfg)
    d, di, n, h = s["d"], s["di"], s["n"], s["h"]
    kind = mixer_kind(cfg, layer)
    shapes = {"ln1_g": (d,), "ln1_b": (d,)}
    if kind in ("ssm", "ssm_mem"):
        shapes.update({
            "in_w": (d, 2 * di), "conv_w": (s["conv"], di), "conv_b": (di,),
            "x_w": (di, s["rank"] + 2 * n), "dt_w": (s["rank"], di),
            "dt_b": (di,), "a_log": (di, n), "d_skip": (di,),
            "out_w": (di, d)})
    elif kind == "gmu":
        shapes.update({"gmu_w1": (d, di), "gmu_w2": (di, d)})
    else:
        if kind == "cross":
            shapes.update({"q_w": (d, d), "q_b": (d,)})
        else:
            width = d + 2 * s["kv"] * h
            shapes.update({"qkv_w": (d, width), "qkv_b": (width,)})
        shapes.update({"o_w": (d, d), "o_b": (d,), "lq1": (h,), "lk1": (h,),
                       "lq2": (h,), "lk2": (h,), "sub_g": (2 * h,)})
    shapes.update({"ln2_g": (d,), "ln2_b": (d,), "mlp_w1": (d, 2 * s["ffn"]),
                   "mlp_w2": (s["ffn"], d)})
    return shapes


def top_shapes(cfg) -> dict:
    s = sizes(cfg)
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("the sambay family ties its head to the embedding")
    return {"embed": (s["vocab"], s["d"]), "norm_g": (s["d"],),
            "norm_b": (s["d"],)}


_GAINS = {"ln1_g": 1.0, "ln2_g": 1.0, "norm_g": 1.0, "sub_g": 1.0,
          "d_skip": 1.0, "a_log": 0.0, "dt_b": -4.0}
_BIASES = ("ln1_b", "ln2_b", "norm_b", "conv_b", "qkv_b", "q_b", "o_b",
           "lq1", "lk1", "lq2", "lk2")


def leaf_draw(cfg, leaf: str):
    """Matrices at ``initializer_range``; gains around their centre (the
    step's bias around -4, so softplus gives steps near 0.018 and a state
    that remembers some fifty tokens; ``A_log`` around 0); biases and the
    lambda vectors at 0.1 (the published initialisation of the lambdas), the
    four convolution taps at 0.5."""
    if leaf in _GAINS:
        return ("gain", _GAINS[leaf])
    if leaf in _BIASES:
        return ("matrix", 0.1)
    if leaf == "conv_w":
        return ("matrix", 0.5)
    return ("matrix", cfg["initializer_range"])


_MIXER = {"in_w": "in_proj.weight", "conv_w": "conv1d.weight",
          "conv_b": "conv1d.bias", "x_w": "x_proj.weight",
          "dt_w": "dt_proj.weight", "dt_b": "dt_proj.bias",
          "a_log": "A_log", "d_skip": "D", "out_w": "out_proj.weight",
          "gmu_w1": "in_proj.weight", "gmu_w2": "out_proj.weight",
          "qkv_w": "Wqkv.weight", "qkv_b": "Wqkv.bias", "q_w": "Wq.weight",
          "q_b": "Wq.bias", "o_w": "out_proj.weight", "o_b": "out_proj.bias",
          "lq1": "lambda_q1", "lk1": "lambda_k1", "lq2": "lambda_q2",
          "lk2": "lambda_k2", "sub_g": "subln.weight"}
_BLOCK = {"ln1_g": "input_layernorm.weight", "ln1_b": "input_layernorm.bias",
          "ln2_g": "post_attention_layernorm.weight",
          "ln2_b": "post_attention_layernorm.bias",
          "mlp_w1": "mlp.fc1.weight", "mlp_w2": "mlp.fc2.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "norm_g": "model.norm.weight",
        "norm_b": "model.norm.bias"}


def parameter_name(leaf: str, layer=None, scanned: bool = False) -> str:
    if leaf in _TOP:
        return _TOP[leaf]
    if scanned:
        raise ValueError("layers of several kinds do not stack")
    if leaf in _BLOCK:
        return f"model.layers.{layer}.{_BLOCK[leaf]}"
    return f"model.layers.{layer}.mixer.{_MIXER[leaf]}"


# -- 3. the equations ---------------------------------------------------------

def position_tables(seq: int, cfg):
    return ()                          # the architecture has no positions


def embed_tokens(ids, top, cfg):
    return top["embed"][ids]


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def state_space(es, u, w, s):
    """u [S, d] -> (output [S, d], scan output y [S, d_inner]); the state
    starts from zero and the scan is token by token."""
    di, n, taps = s["di"], s["n"], s["conv"]
    az = es("se,ef->sf", u, w["in_w"])
    a, z = az[:, :di], az[:, di:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), F32), a])
    a = jax.nn.silu(sum(w["conv_w"][k] * padded[k:k + u.shape[0]]
                        for k in range(taps)) + w["conv_b"])
    rbc = es("sf,fr->sr", a, w["x_w"])
    r, b_t, c_t = jnp.split(rbc, [s["rank"], s["rank"] + n], axis=1)
    dt = jax.nn.softplus(es("sr,rf->sf", r, w["dt_w"]) + w["dt_b"])
    a_neg = -jnp.exp(w["a_log"])

    def step(h, xs):
        dt_t, a_t, bt, ct = xs
        h = jnp.exp(dt_t[:, None] * a_neg) * h \
            + (dt_t * a_t)[:, None] * bt[None, :]
        return h, es("fn,n->f", h, ct) + w["d_skip"] * a_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), F32), (dt, a, b_t, c_t))
    return es("sf,fe->se", y * jax.nn.silu(z), w["out_w"]), y


def _query_block(s: int) -> int:
    for blk in (512, 256, 128):
        if s > blk and s % blk == 0:
            return blk
    return s


def differential_attention(es, q, k, v, w, layer, s, window=None):
    """q [S, H, h], k and v [S, KV, h] of one sequence -> [S, d]. Queries go
    in blocks of at most 512, so that the scores of a long sequence fit."""
    seq, h, groups = q.shape[0], s["h"], s["kv"] // 2
    qg = q.reshape(seq, groups, 2, 2, h)       # group, pair in group, branch
    kg = k.reshape(seq, groups, 2, h)          # group, branch
    vg = v.reshape(seq, groups, 2 * h)         # concat(v_2g, v_2g+1)
    lam_0 = lam0(layer)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam_0
    kpos = jnp.arange(seq)

    def block(args):
        qb, qpos = args
        scores = es("sgpjd,tgjd->gpjst", qb, kg) / math.sqrt(h)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= qpos[:, None] - kpos[None, :] < window
        probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), -1)
        att = es("gpjst,tge->sgpje", probs, vg)
        diff = att[..., 0, :] - lam * att[..., 1, :]
        rms = jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True) + 1e-5)
        return ((1.0 - lam_0) * diff * rms * w["sub_g"]).reshape(
            qb.shape[0], -1)

    blk = _query_block(seq)
    out = jax.lax.map(block, (qg.reshape(seq // blk, blk, groups, 2, 2, h),
                              kpos.reshape(seq // blk, blk)))
    return es("sf,fe->se", out.reshape(seq, -1), w["o_w"]) + w["o_b"]


def layer_forward(x, w, tables, cfg, layer, precision="f32"):
    """One block over one sequence. x [S, width] float32: the hidden state
    in the first d columns, then (from layer L/2) the memory M and (from
    layer L/2 + 1) the kept K and V."""
    es = functools.partial(einsum, precision)
    s = sizes(cfg)
    d, di, h, kv = s["d"], s["di"], s["h"], s["kv"]
    seq, eps = x.shape[0], cfg["layer_norm_eps"]
    kind = mixer_kind(cfg, layer)
    hid, kept = x[:, :d], x[:, d:]
    u = _layer_norm(hid, w["ln1_g"], w["ln1_b"], eps)
    if kind in ("ssm", "ssm_mem"):
        mixed, y = state_space(es, u, w, s)
        if kind == "ssm_mem":
            kept = y
    elif kind == "gmu":
        gate = jax.nn.silu(es("se,ef->sf", u, w["gmu_w1"]))
        mixed = es("sf,fe->se", gate * kept[:, :di], w["gmu_w2"])
    else:
        if kind == "cross":
            q = es("se,ef->sf", u, w["q_w"]) + w["q_b"]
            k, v = jnp.split(kept[:, di:], 2, axis=1)
        else:
            qkv = es("se,ef->sf", u, w["qkv_w"]) + w["qkv_b"]
            q, k, v = jnp.split(qkv, [d, d + kv * h], axis=1)
            if kind == "full":
                kept = jnp.concatenate([kept, k, v], axis=1)
        mixed = differential_attention(
            es, q.reshape(seq, s["heads"], h), k.reshape(seq, kv, h),
            v.reshape(seq, kv, h), w, layer, s,
            window=s["window"] if kind == "window" else None)
    hid = hid + mixed
    u = _layer_norm(hid, w["ln2_g"], w["ln2_b"], eps)
    gp = es("se,ef->sf", u, w["mlp_w1"])
    hid = hid + es("sf,fe->se", jax.nn.silu(gp[:, :s["ffn"]])
                   * gp[:, s["ffn"]:], w["mlp_w2"])
    return jnp.concatenate([hid, kept], axis=1)


def head_logits(x, top, cfg, precision="f32"):
    """Final norm and tied head over rows x [N, width]: the first d columns
    are the hidden state."""
    x = _layer_norm(x[:, :cfg["hidden_size"]], top["norm_g"], top["norm_b"],
                    cfg["layer_norm_eps"])
    return einsum(precision, "ne,ve->nv", x, top["embed"])


# -- the counts: operations and bytes the equations need ----------------------

def layer_counts(cfg) -> dict:
    """How many layers of each kind of mixer the configuration has."""
    kinds = [mixer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    return {k: kinds.count(k) for k in ("ssm", "ssm_mem", "window", "full",
                                        "gmu", "cross")}


def layer_matmul_params(cfg, layer: int) -> int:
    """Weights of one block that a token is multiplied through."""
    s = sizes(cfg)
    d, di, n = s["d"], s["di"], s["n"]
    kind = mixer_kind(cfg, layer)
    if kind in ("ssm", "ssm_mem"):
        mixer = d * 2 * di + di * (s["rank"] + 2 * n) + s["rank"] * di \
            + di * d
    elif kind == "gmu":
        mixer = 2 * d * di
    elif kind == "cross":
        mixer = 2 * d * d
    else:
        mixer = d * (d + 2 * s["kv"] * s["h"]) + d * d
    return mixer + 3 * d * s["ffn"]


def matmul_params(cfg) -> int:
    """All blocks plus the tied head; the embedding lookup is a gather."""
    return sum(layer_matmul_params(cfg, i)
               for i in range(cfg["num_hidden_layers"])) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def kv_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """K and V of one token in ONE layer that holds them."""
    s = sizes(cfg)
    return 2 * s["kv"] * s["h"] * itemsize


def ssm_state_bytes(cfg, itemsize: int = 2) -> int:
    """One sequence's recurrent state in one state-space layer: h in
    float32 and the last rows of the convolution's input."""
    s = sizes(cfg)
    return s["di"] * s["n"] * 4 + (s["conv"] - 1) * s["di"] * itemsize


def slot_state_bytes(cfg, itemsize: int = 2) -> int:
    """What one running sequence holds whatever its length: the state of
    every state-space layer and a ring of ``sliding_window`` rows a window
    layer."""
    c = layer_counts(cfg)
    return (c["ssm"] + c["ssm_mem"]) * ssm_state_bytes(cfg, itemsize) \
        + c["window"] * cfg["sliding_window"] * kv_bytes_per_row(cfg,
                                                                 itemsize)


def decode_state_bytes(cfg, running: int, context_rows: int,
                       window_rows: int, itemsize: int = 2) -> int:
    """Bytes the token mixers of decode steps must move beside the weights:
    every state-space state read and written, the window rows read and one
    row written a window layer, the shared K/V rows read once a reading
    layer (the full layer and each cross layer) and one row written.
    ``running``: sequences summed over the steps; ``context_rows``: their
    cached rows summed likewise (this step's among them); ``window_rows``:
    the rows inside the window summed likewise."""
    c = layer_counts(cfg)
    row = kv_bytes_per_row(cfg, itemsize)
    ssm = (c["ssm"] + c["ssm_mem"]) * 2 * ssm_state_bytes(cfg, itemsize)
    return running * ssm \
        + c["window"] * (window_rows + running) * row \
        + ((c["full"] + c["cross"]) * context_rows + running) * row


def decode_step_bytes(cfg, steps: int, running: int, context_rows: int,
                      window_rows: int, itemsize: int = 2) -> int:
    """Bytes ``steps`` decode steps must move: every matmul weight once a
    step, plus what ``decode_state_bytes`` counts."""
    return steps * matmul_params(cfg) * itemsize \
        + decode_state_bytes(cfg, running, context_rows, window_rows,
                             itemsize)


def ssm_scan_cost(cfg, tokens: int, itemsize: int = 2):
    """(flops, bytes) of ONE state-space layer's scan over a chunk of
    ``tokens`` from a carried state: a token costs, for each of d_inner x
    n state entries, the step's product and exponential (2), the decay and
    the input's products and sum (4) and the output's product and sum (2),
    plus the skip; read are dt (float32), the convolved input, B and C and
    the state, written the output and the state."""
    s = sizes(cfg)
    di, n = s["di"], s["n"]
    flops = tokens * (8 * di * n + 2 * di)
    nbytes = tokens * (di * 4 + di * itemsize + 2 * n * itemsize
                       + di * itemsize) + 2 * di * n * 4
    return flops, nbytes
