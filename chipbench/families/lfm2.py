"""Family ``lfm2``: LFM2's mixture-of-experts decoder (``model_type:
lfm2_moe``): a *gated short convolution* as most layers' operator, grouped-
query attention in the others (``layer_types``), a dense SwiGLU in the
leading ``num_dense_layers`` layers and a sparse expert layer behind a
sigmoid router with a selection-only bias in the rest.

Equations, float32, over one sequence x [S, d]; ``RMS(x; g) = g x /
sqrt(mean(x^2) + norm_eps)``; L = ``conv_L_cache``:

* Layer i: ``u = RMS(x; g_op)``; ``x <- x + Operator_i(u)``; ``x <- x +
  FFN_i(RMS(x; g_ffn))``. ``Operator_i`` is attention where
  ``layer_types[i] == "full_attention"``, else the short convolution;
  ``FFN_i`` the dense MLP for ``i < num_dense_layers``, else the experts.
* Gated short convolution (``conv_bias`` false: no bias anywhere): ``[B | C
  | X] = u W_in`` (``W_in`` [d, 3d], the thirds in that order); ``z_t = B_t
  X_t``; ``c_t = sum_{k < L} w[:, k] z_{t-(L-1)+k}`` a channel (``w`` [d,
  L], depthwise, causal: ``z`` before the first row is zero); output ``(C_t
  c_t) W_out``. No activation between. The state of a layer and sequence
  after row t: ``z_{t-L+2} .. z_t``, L - 1 rows of d.
* Attention: ``q = u W_q`` (H heads of D = d / H), ``k = u W_k``, ``v = u
  W_v`` (KV heads of D), no bias; (assumed) every head's ``q`` and ``k``
  through ``RMS`` over its D dims with one gain for all heads (``g_q``,
  ``g_k``) before the rotation; rotation over all D dims, pairs ``(j, j +
  D/2)``, angles ``t * rope_theta^(-2j/D)`` made in float64; causal softmax
  of ``q . k / sqrt(D)`` in float32, query head n reads key head ``n // (H /
  KV)``; ``x <- x + concat(heads) W_o``.
* Dense MLP: ``(silu(h W1) * (h W3)) W2`` at ``intermediate_size``
  (``mlp_w1`` holds ``[W1 | W3]``).
* Experts: ``s = sigmoid(h W_r)`` in float32; chosen = the
  ``num_experts_per_tok`` largest of ``s + b`` (``use_expert_bias``: ``b``
  takes part in the choice only; ties to the lower index); gates ``s_e /
  (sum of the chosen s + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``sum_e gate_e W2_e (silu(W1_e h) * W3_e h)``
  at ``moe_intermediate_size`` (``exp_w1`` holds ``[W1 | W3]``). No shared
  expert.
* Final ``RMS``, then logits ``x E^T`` with the embedding ``E`` (tied).

An expert multiplies the rows that chose it, gathered, with room for twice
its even share and every row where more chose it (the sum is the equations'
whatever the routing); attention scores are made for a block of queries at a
time. Nothing of the program is imported here but inside ``program_model``.
The count functions at the end are the numerators of this family's
per-layer metrics: what the equations need, whatever implements them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import F32, einsum
from .mellum import _rms, _rope, embed_tokens, share_of_least  # noqa: F401

SPANS = ("serving.state_restore", "serving.state_snapshot")
SCOPES = ("short_conv", "in_proj", "gate_conv", "out_proj", "state_read",
          "state_write", "full_attention", "qkv_rope", "kv_write", "router",
          "experts_routed")
# what this family's readers under chipbench/metrics/ read
COUNTERS = tuple(
    (f"{key}_{phase}", f"serving.{key}_total", {"phase": phase})
    for key in ("moe_assignments", "moe_assignments_local")
    for phase in ("decode", "prefill")) + (
    ("moe_experts_touched", "serving.moe_experts_touched_total", {}),
    ("moe_experts_touched_prefill",
     "serving.moe_experts_touched_prefill_total", {}),
    ("moe_expert_tokens_max", "serving.moe_expert_tokens_max", {}),
    ("kv_cache_bytes", "serving.kv_cache_bytes", {"group": "full"}),
    ("state_snapshot_bytes", "serving.state_snapshot_bytes", {}),
    ("recurrent_state_bytes", "serving.recurrent_state_bytes", {}),
    ("state_snapshots_held", "serving.state_snapshots_held", {}),
    ("state_snapshots_taken", "serving.state_snapshots_taken_total", {}),
    ("state_snapshots_restored", "serving.state_snapshots_restored_total",
     {}),
    ("state_snapshots_reclaimed", "serving.state_snapshots_reclaimed_total",
     {}),
    ("prefix_matches", "serving.prefix_matches_total", {}),
    ("prefix_hits_cut", "serving.prefix_hits_cut_total",
     {"why": "no_state_snapshot"}),
    ("prefix_rows_cut", "serving.prefix_rows_cut_total", {}),
    ("prefix_evictions", "serving.prefix_evictions", {}),
)
DISCRETE_CHOICES = ("router_topk",)

CONV, FULL = "conv", "full_attention"


# -- sizes --------------------------------------------------------------------

def sizes(cfg) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "heads": heads, "kv": cfg["num_key_value_heads"],
            "hd": d // heads, "taps": cfg["conv_L_cache"],
            "ffn": cfg["intermediate_size"], "dense": cfg["num_dense_layers"],
            "experts": cfg["num_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "effn": cfg["moe_intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def is_conv(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == CONV


def is_dense(cfg, layer: int) -> bool:
    return layer < cfg["num_dense_layers"]


# -- 1. the program's model ---------------------------------------------------

def program_model(cfg: dict, **extra):
    from paddle_tpu.models.lfm2 import Lfm2Config, Lfm2ForCausalLM
    s = sizes(cfg)
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default" or cfg.get("conv_bias"):
        raise ValueError("the lfm2 family: plain rotary tables, no bias")
    return Lfm2ForCausalLM(Lfm2Config(
        vocab_size=s["vocab"], hidden_size=s["d"],
        num_hidden_layers=s["layers"], num_attention_heads=s["heads"],
        num_key_value_heads=s["kv"], layer_types=tuple(cfg["layer_types"]),
        conv_L_cache=s["taps"], intermediate_size=s["ffn"],
        num_dense_layers=s["dense"], num_experts=s["experts"],
        num_experts_per_tok=s["per_tok"], moe_intermediate_size=s["effn"],
        norm_topk_prob=cfg["norm_topk_prob"],
        use_expert_bias=cfg["use_expert_bias"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_eps=cfg["norm_eps"], rope_theta=float(rope["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg.get("initializer_range", 0.02),
        **{k: cfg[k] for k in ("prefill_key_block", "snapshot_rows")
           if k in cfg},
        **extra))


# -- 2. the leaves ------------------------------------------------------------

def layer_kind(cfg, layer: int):
    return ("conv" if is_conv(cfg, layer) else "attn") \
        + ("_dense" if is_dense(cfg, layer) else "")


def layer_shapes(cfg, layer: int) -> dict:
    s = sizes(cfg)
    d, hd = s["d"], s["hd"]
    shapes = {"ln1_g": (d,), "ln2_g": (d,)}
    if is_conv(cfg, layer):
        shapes.update({"in_w": (d, 3 * d), "conv_w": (d, s["taps"]),
                       "out_w": (d, d)})
    else:
        shapes.update({"q_w": (d, s["heads"] * hd), "k_w": (d, s["kv"] * hd),
                       "v_w": (d, s["kv"] * hd), "q_g": (hd,), "k_g": (hd,),
                       "o_w": (s["heads"] * hd, d)})
    if is_dense(cfg, layer):
        shapes.update({"mlp_w1": (d, 2 * s["ffn"]), "mlp_w2": (s["ffn"], d)})
    else:
        f = s["effn"]
        shapes.update({"router_w": (d, s["experts"]),
                       "exp_w1": (s["experts"], d, 2 * f),
                       "exp_w2": (s["experts"], f, d)})
        if cfg["use_expert_bias"]:
            shapes["router_b"] = (s["experts"],)
    return shapes


def top_shapes(cfg) -> dict:
    """The embedding is the head too (assumed: tied)."""
    s = sizes(cfg)
    return {"embed": (s["vocab"], s["d"]), "norm_g": (s["d"],)}


_GAINS = ("ln1_g", "ln2_g", "q_g", "k_g", "norm_g")
_RESIDUAL_OUT = ("o_w", "out_w", "exp_w2", "mlp_w2")
EXPERT_BIAS_STD = 0.03
EXPERT_OUT_SCALE = 0.5


def leaf_draw(cfg, leaf: str):
    """Norm gains around one, the taps around 1 / L, the selection bias
    around 0 at ``expert_bias_std`` (it has no published distribution: at
    this scale it changes the chosen set for an eighth of the tokens at
    rehearsal size, ``tests/test_lfm2.py`` counts it), every matrix at the
    configuration's ``initializer_range``, the embedding among them (it is
    the head too: at 0.02 the logits spread by about one), but those that
    write into the residual stream (``W_o``, ``W_out``, every ``W2``), drawn
    at ``initializer_range / sqrt(2 x num_hidden_layers)`` by the depth as
    run, as the family ``keye``'s are (PERF.md section 6, PR 42), and the
    experts' ``W2`` at half of that again. Chosen from this PR's first round
    on the chip (PERF.md section 6, PR 44): the router's fourth and fifth
    score lie so close that bfloat16 rounding swaps them in some layer for
    a third of the tokens, every swap moves the logits by a whole expert's
    contribution, and with the experts' ``W2`` at the other matrices' scale
    the program read within 2.4 to 2.6 times of float8 at the 99th
    percentile of the gap (9 sound runs, 5 controls), the reference at
    bfloat16 operands the same: under the three the rule asks. At half, a
    swap costs half."""
    if leaf in _GAINS:
        return ("gain", 1.0)
    if leaf == "conv_w":
        return ("gain", 1.0 / cfg["conv_L_cache"])
    if leaf == "router_b":
        return ("matrix", cfg.get("expert_bias_std", EXPERT_BIAS_STD))
    scale = cfg.get("initializer_range", 0.02)
    if leaf in _RESIDUAL_OUT:
        scale /= math.sqrt(2 * cfg["num_hidden_layers"])
    if leaf == "exp_w2":
        scale *= EXPERT_OUT_SCALE
    return ("matrix", scale)


_CONV = {"in_w": "in_proj", "conv_w": "conv", "out_w": "out_proj"}
_ATTN = {"q_w": "q_proj", "k_w": "k_proj", "v_w": "v_proj", "o_w": "out_proj",
         "q_g": "q_layernorm", "k_g": "k_layernorm"}
_FFN = {"mlp_w1": "fc1", "mlp_w2": "fc2", "router_w": "gate",
        "router_b": "expert_bias", "exp_w1": "experts_fc1",
        "exp_w2": "experts_fc2"}
_BLOCK = {"ln1_g": "operator_norm", "ln2_g": "ffn_norm"}
_TOP = {"embed": "model.embed_tokens.weight",
        "norm_g": "model.embedding_norm.weight"}


def parameter_name(leaf: str, layer=None, scanned: bool = False) -> str:
    if leaf in _TOP:
        return _TOP[leaf]
    if scanned:
        raise ValueError("layers of several kinds do not stack")
    if leaf in _BLOCK:
        return f"model.layers.{layer}.{_BLOCK[leaf]}.weight"
    if leaf in _CONV:
        return f"model.layers.{layer}.conv.{_CONV[leaf]}.weight"
    if leaf in _ATTN:
        return f"model.layers.{layer}.self_attn.{_ATTN[leaf]}.weight"
    return f"model.layers.{layer}.feed_forward.{_FFN[leaf]}.weight"


# -- 3. the equations ---------------------------------------------------------

def position_tables(seq: int, cfg):
    """(cos, sin) [seq, D/2], angles made in float64."""
    dim = sizes(cfg)["hd"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    ang = np.outer(np.arange(seq, dtype=np.float64),
                   theta ** (-np.arange(dim // 2, dtype=np.float64) * 2.0
                             / dim))
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def short_conv(es, u, w, cfg, reset_at=None):
    """The operator's output over rows u [S, d] (normed). ``reset_at`` r
    makes the rows from r on read ``z`` before r as zero: a sequence resumed
    at r from an empty state (the omission script's)."""
    seq, d = u.shape
    taps = cfg["conv_L_cache"]
    bcx = es("se,ef->sf", u, w["in_w"])
    z = bcx[:, :d] * bcx[:, 2 * d:]
    back = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    t = jnp.arange(seq)
    c = jnp.zeros_like(z)
    for k in range(taps):
        term = w["conv_w"][:, k][None, :] * back[k:k + seq]
        if reset_at is not None:        # the tap reads row t - (L-1) + k
            src = t - (taps - 1) + k
            term = jnp.where(((t >= reset_at) & (src < reset_at))[:, None],
                             0.0, term)
        c = c + term
    return es("se,ef->sf", bcx[:, d:2 * d] * c, w["out_w"])


_QUERY_BLOCK = 128


def attention(es, u, w, tables, cfg):
    """The attention operator's output over rows u [S, d] (normed), S whole
    blocks of queries."""
    s = sizes(cfg)
    total = u.shape[0]
    heads, kvh, hd, eps = s["heads"], s["kv"], s["hd"], cfg["norm_eps"]
    cos, sin = tables
    big = min(total, _QUERY_BLOCK)
    q = _rope(_rms(es("se,ef->sf", u, w["q_w"]).reshape(total, heads, hd),
                   w["q_g"], eps), cos, sin)
    k = _rope(_rms(es("se,ef->sf", u, w["k_w"]).reshape(total, kvh, hd),
                   w["k_g"], eps), cos, sin)
    v = es("se,ef->sf", u, w["v_w"]).reshape(total, kvh, hd)

    def queries(args):
        qb, start = args                                  # [big, H, D]
        ok = jnp.arange(total)[None, :] <= (start + jnp.arange(big))[:, None]
        qg = qb.reshape(big, kvh, heads // kvh, hd)
        att = es("sgrd,tgd->grst", qg, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(ok, att, -jnp.inf), -1)
        return es("grst,tgd->sgrd", probs, v).reshape(big, heads * hd)

    ctx = jax.lax.map(queries, (q.reshape(total // big, big, heads, hd),
                                jnp.arange(0, total, big)))
    return es("sf,fe->se", ctx.reshape(total, heads * hd), w["o_w"])


def router(es, u, w, s, cfg, keep=None, bias="choice"):
    """(chosen [S, k], their gates [S, k]) of rows u. ``keep`` chooses
    fewer than the configuration says; ``bias`` "none" leaves the bias out
    of the choice, "gates" lets it into the gates too (the omission
    script's departures)."""
    score = jax.nn.sigmoid(es("se,er->sr", u, w["router_w"]))
    b = w["router_b"] if "router_b" in w and bias != "none" else 0.0
    _, chosen = jax.lax.top_k(score + b, keep or s["per_tok"])
    picked = jnp.take_along_axis(score + b if bias == "gates" else score,
                                 chosen, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
    return chosen, cfg["routed_scaling_factor"] * picked


def _swiglu(es, x, w1, w2):
    gp = es("se,ef->sf", x, w1.astype(F32))
    f = gp.shape[-1] // 2
    return es("sf,fe->se", jax.nn.silu(gp[:, :f]) * gp[:, f:],
              w2.astype(F32))


_EXPERT_ROOM = 2


def experts_by_rows(es, u, w, s, cfg, keep=None, bias="choice", held=None):
    """``sum_e gate_e SwiGLU_e(u)`` over rows u [S, d], one expert after
    another, each over the rows that chose it alone: they are gathered (in
    row order, ``jnp.nonzero`` at a fixed size), multiplied and added back
    at the row's gate. The room is ``_EXPERT_ROOM`` times an expert's even
    share of the rows; an expert that more rows chose than that multiplies
    every row at its gate instead, 0 where the row did not choose it, so
    the sum is the equations' whatever the routing. ``held`` (start, count)
    adds the experts of that share alone (the shares test of the
    ``model-configs`` guide). The stacked weights stay in the bfloat16 they
    were drawn in and are widened an expert at a time."""
    chosen, gates = router(es, u, w, s, cfg, keep, bias)
    rows = u.shape[0]
    room = min(rows, -(-_EXPERT_ROOM * rows * chosen.shape[1]
                       // s["experts"]))
    first, count = held or (0, s["experts"])

    def one(y, xs):
        e, w1, w2 = xs
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
        mine = jnp.any(chosen == e, -1)

        def gathered(y):
            at = jnp.nonzero(mine, size=room, fill_value=rows)[0]
            out = _swiglu(es, u.at[at].get(mode="fill", fill_value=0.0),
                          w1, w2)
            return y.at[at].add(
                gate.at[at].get(mode="fill", fill_value=0.0)[:, None] * out,
                mode="drop", indices_are_sorted=True, unique_indices=True)

        return jax.lax.cond(
            jnp.sum(mine) > room,
            lambda y: y + gate[:, None] * _swiglu(es, u, w1, w2),
            gathered, y), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(first, first + count),
         w["exp_w1"][first:first + count].astype(jnp.bfloat16),
         w["exp_w2"][first:first + count].astype(jnp.bfloat16)))
    return y


def layer_forward(x, w, tables, cfg, layer, precision="f32", reset_at=None,
                  experts_kept=None, bias="choice"):
    """One block over one sequence x [S, d] float32 (``layer`` says which
    operator and which FFN). ``reset_at``, ``experts_kept`` and ``bias`` are
    the omission script's departures."""
    es = functools.partial(einsum, precision)
    s = sizes(cfg)
    seq = x.shape[0]
    eps = cfg["norm_eps"]
    cos, sin = tables
    pad = -seq % min(seq, _QUERY_BLOCK)
    if pad:                 # rows past the end change nothing before them
        x = jnp.pad(x, ((0, pad), (0, 0)))
        cos, sin = (jnp.pad(t, ((0, pad), (0, 0))) for t in (cos, sin))
    u = _rms(x, w["ln1_g"], eps)
    if is_conv(cfg, layer):
        x = x + short_conv(es, u, w, cfg, reset_at)
    else:
        x = x + attention(es, u, w, (cos[:x.shape[0]], sin[:x.shape[0]]),
                          cfg)
    h = _rms(x, w["ln2_g"], eps)
    if is_dense(cfg, layer):
        x = x + _swiglu(es, h, w["mlp_w1"], w["mlp_w2"])
    else:
        x = x + experts_by_rows(es, h, w, s, cfg, experts_kept, bias)
    return x[:seq]


def head_logits(x, top, cfg, precision="f32"):
    """Final norm and the tied head over rows x [N, d]."""
    return einsum(precision, "ne,ve->nv",
                  _rms(x, top["norm_g"], cfg["norm_eps"]), top["embed"])


# -- the counts: operations and bytes the equations need ----------------------

def layers_of(cfg) -> dict:
    """{"conv": n, "attn": n, "dense": n, "moe": n} layers of each kind."""
    n = cfg["num_hidden_layers"]
    conv = sum(is_conv(cfg, i) for i in range(n))
    dense = sum(is_dense(cfg, i) for i in range(n))
    return {"conv": conv, "attn": n - conv, "dense": dense, "moe": n - dense}


def short_conv_params(cfg) -> int:
    """One convolution operator: ``W_in``, ``W_out`` and the taps."""
    s = sizes(cfg)
    return 4 * s["d"] * s["d"] + s["d"] * s["taps"]


def attention_params(cfg) -> int:
    """The four projections of one attention operator."""
    s = sizes(cfg)
    return 2 * s["d"] * s["heads"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    s = sizes(cfg)
    return 3 * s["d"] * s["effn"]


def fixed_matmul_params(cfg) -> int:
    """Weights every decode step multiplies through whatever the routing:
    every operator, the routers, the dense MLPs, the head (the embedding
    read as a matrix; its lookup is a gather)."""
    s, n = sizes(cfg), layers_of(cfg)
    return n["conv"] * short_conv_params(cfg) \
        + n["attn"] * attention_params(cfg) \
        + n["moe"] * s["d"] * s["experts"] \
        + n["dense"] * 3 * s["d"] * s["ffn"] + s["d"] * s["vocab"]


def kv_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """One token's K and V rows in ONE attention layer."""
    s = sizes(cfg)
    return 2 * s["kv"] * s["hd"] * itemsize


def state_bytes_per_sequence(cfg, itemsize: int = 2) -> int:
    """The convolution layers' state of one sequence, whatever its length:
    L - 1 rows of d a layer (a snapshot is as much)."""
    s = sizes(cfg)
    return layers_of(cfg)["conv"] * (s["taps"] - 1) * s["d"] * itemsize


def cache_bytes_per_row(cfg, snapshot_rows: int, itemsize: int = 2) -> float:
    """What one cached token has to hold, all layers: its K and V rows and
    its share of a snapshot every ``snapshot_rows`` rows."""
    return layers_of(cfg)["attn"] * kv_bytes_per_row(cfg, itemsize) \
        + state_bytes_per_sequence(cfg, itemsize) / snapshot_rows


def kv_rows_read(cfg, context_tokens: int) -> int:
    """(query, row) pairs the decode steps' attention has to read, all its
    layers together: every resident row (``context_tokens``: the rows
    before each decoded token, summed over the tokens)."""
    return layers_of(cfg)["attn"] * context_tokens


def kv_attention_cost(cfg, rows_read: int, itemsize: int = 2):
    """(flops, bytes) of the decode steps' attention over ``rows_read``:
    every row's K and V read once; H heads x D x 2 for the score and for
    the output."""
    s = sizes(cfg)
    return rows_read * s["heads"] * s["hd"] * 2 * 2, \
        rows_read * kv_bytes_per_row(cfg, itemsize)


def short_conv_decode_bytes(cfg, steps: int, sequence_steps: int,
                            itemsize: int = 2) -> int:
    """Bytes the convolution operators of ``steps`` decode steps must move:
    their weights once a step, every running sequence's state read and
    written (``sequence_steps``: running sequences summed over the steps)."""
    return steps * layers_of(cfg)["conv"] * short_conv_params(cfg) \
        * itemsize + 2 * sequence_steps * state_bytes_per_sequence(cfg,
                                                                   itemsize)


def short_conv_prefill_flops(cfg, rows: int) -> int:
    """Operations of the convolution operators over ``rows`` prefilled rows:
    two a weight, row and layer."""
    return 2 * short_conv_params(cfg) * rows * layers_of(cfg)["conv"]


def decode_step_bytes(cfg, steps: int, experts_touched: int,
                      rows_read: int, sequence_steps: int,
                      itemsize: int = 2) -> int:
    """Bytes ``steps`` decode steps must move: the fixed weights once a
    step, each touched expert's weights, the K and V of every row read,
    every running sequence's convolution state read and written."""
    return itemsize * (steps * fixed_matmul_params(cfg)
                       + experts_touched * expert_params(cfg)) \
        + rows_read * kv_bytes_per_row(cfg, itemsize) \
        + 2 * sequence_steps * state_bytes_per_sequence(cfg, itemsize)


def routed_experts_cost(cfg, assignments: int, experts_touched: int,
                        itemsize: int = 2):
    """(flops, bytes) of the grouped product: an assignment is a token
    through one expert's three matrices; a touched expert's weights are
    read once a step (or chunk) and layer."""
    return assignments * expert_params(cfg) * 2, \
        experts_touched * expert_params(cfg) * itemsize


def routed_experts_roofline(run, executable: str, phase: str, touched: str):
    """What ``moe_experts_roofline.agent`` (decode steps) and
    ``moe_experts_prefill_roofline.agent`` (chunks) read: the touched
    experts' weights and the assignments' operations of ``phase`` at the
    chip's peaks over ``executable``'s device time under ``experts_routed``,
    in percent; None where the trace or the counter ``touched`` has
    nothing."""
    from .. import costs
    c = run.get("counters", {})
    if not run.get("peaks") or not c.get(touched):
        return None
    flops, nbytes = routed_experts_cost(
        run["cfg"], c.get(f"moe_assignments_local_{phase}", 0), c[touched])
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    return share_of_least(run, executable, ("experts_routed",), least,
                          f"experts_routed_{phase}", bound)

