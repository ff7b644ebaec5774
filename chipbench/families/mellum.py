"""Family ``mellum``: Mellum 2's decoder (``model_type: mellum``): grouped-query
attention whose layers are a sliding window or the whole sequence by
``layer_types``, rotary tables by layer kind (``rope_parameters``), and a
sparse expert layer in every block (``mlp_layer_types``).

Equations, float32, for layer ``l`` of kind ``layer_types[l]`` over one
sequence x [S, d]; ``RMS`` is RMSNorm with a gain and ``rms_norm_eps``:

* ``h = RMS(x; g1)``; ``q = h W_q`` (H heads of D), ``k = h W_k``, ``v = h
  W_v`` (KV heads of D), no bias.
* (assumed) every head's ``q`` and ``k`` pass ``RMS`` over their D dims with
  one gain for all heads (``g_q``, ``g_k``) before the rotation.
* Rotation over all D dims, pairs ``(i, i + D/2)``: ``[x1 | x2] -> [x1 c - x2
  s | x2 c + x1 s]``. Window layers: angles ``t * theta^(-2i/D)``. Full
  layers, YaRN (arXiv:2309.00071, as the public implementations compute it):
  ``low, high`` = floor / ceil of ``D ln(original / (beta 2 pi)) / (2 ln
  theta)`` at ``beta_fast`` / ``beta_slow`` (18 and 35 at the published
  sizes), ``r_i = clip((i - low) / (high - low), 0, 1)`` for i in 0 .. D/2,
  inverse frequency ``(1 - r_i) f_i + r_i f_i / factor`` with ``f_i =
  theta^(-2i/D)``, made in float64; cos and sin both times
  ``attention_factor``.
* Scores ``q . k / sqrt(D)``, H / KV query heads a key head, causal; a window
  layer's query t reads keys s with ``0 <= t - s < sliding_window``. Softmax
  in float32. ``x <- x + concat(heads) W_o``.
* ``h2 = RMS(x; g2)``; ``p = softmax(h2 W_r)`` over all ``num_experts`` in
  float32; the ``num_experts_per_tok`` largest (ties to the lower index);
  gates ``p_e / sum of the chosen p`` (``norm_topk_prob``); ``x <- x + sum_e
  gate_e W2_e (silu(W1g_e h2) * W1u_e h2)``, width ``moe_intermediate_size``
  (``exp_w1`` holds ``[W1g | W1u]``). No shared expert, no dense layer.
* Final ``RMS``, untied head. The multi-token-prediction head is not loaded.

A long sequence fits because scores are made for a block of queries at a
time: a window layer's block against the rows its windows reach, a full
layer's against every row before its end.

Nothing of the program is imported here but inside ``program_model``. The
count functions at the end are the numerators of this family's per-layer
metrics: what the equations need, whatever implements them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import F32, einsum

SPANS = ("serving.release_window",)
SCOPES = ("qkv_rope", "window_attention", "full_attention", "kv_write",
          "router", "experts_routed")
_GROUPS = ("full", "window")
# what this family's readers under chipbench/metrics/ read
COUNTERS = tuple(
    (f"moe_assignments_local_{phase}", "serving.moe_assignments_local_total",
     {"phase": phase}) for phase in ("decode", "prefill")) + (
    ("moe_experts_touched", "serving.moe_experts_touched_total", {}),
    ("moe_experts_touched_prefill",
     "serving.moe_experts_touched_prefill_total", {}),
    ("moe_expert_tokens_max", "serving.moe_expert_tokens_max", {}),
    ("kv_bytes_per_resident_row", "serving.kv_bytes_per_resident_row", {}),
    ("prefix_matches", "serving.prefix_matches_total", {}),
    ("prefix_hits_cut", "serving.prefix_hits_cut_total",
     {"why": "window_pages_reclaimed"}),
)
DISCRETE_CHOICES = ("router_topk",)

WINDOW, FULL = "sliding_attention", "full_attention"


# -- sizes --------------------------------------------------------------------

def sizes(cfg) -> dict:
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "window": cfg["sliding_window"], "experts": cfg["num_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "effn": cfg["moe_intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def is_window(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == WINDOW


def _yarn(cfg) -> dict:
    return {k: v for k, v in cfg["rope_parameters"][FULL].items()
            if k not in ("rope_type", "rope_theta")}


# -- 1. the program's model ---------------------------------------------------

def program_model(cfg: dict, **extra):
    from paddle_tpu.models.mellum import MellumConfig, MellumForCausalLM
    s = sizes(cfg)
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("every layer of the mellum family is sparse")
    return MellumForCausalLM(MellumConfig(
        vocab_size=s["vocab"], hidden_size=s["d"],
        num_hidden_layers=s["layers"], num_attention_heads=s["heads"],
        num_key_value_heads=s["kv"], head_dim=s["hd"],
        layer_types=tuple(cfg["layer_types"]), sliding_window=s["window"],
        num_experts=s["experts"], num_experts_per_tok=s["per_tok"],
        moe_intermediate_size=s["effn"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"][WINDOW]["rope_theta"]),
        yarn=_yarn(cfg),
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg.get("initializer_range", 0.02),
        **{k: cfg[k] for k in ("prefill_key_block",) if k in cfg},
        **extra))


# -- 2. the leaves ------------------------------------------------------------

def layer_kind(cfg, layer: int):
    return "window" if is_window(cfg, layer) else "full"


def layer_shapes(cfg, layer: int) -> dict:
    s = sizes(cfg)
    d, hd, f = s["d"], s["hd"], s["effn"]
    return {"ln1_g": (d,), "q_w": (d, s["heads"] * hd),
            "k_w": (d, s["kv"] * hd), "v_w": (d, s["kv"] * hd),
            "q_g": (hd,), "k_g": (hd,), "o_w": (s["heads"] * hd, d),
            "ln2_g": (d,), "router_w": (d, s["experts"]),
            "exp_w1": (s["experts"], d, 2 * f),
            "exp_w2": (s["experts"], f, d)}


def top_shapes(cfg) -> dict:
    s = sizes(cfg)
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the mellum family's head is untied")
    return {"embed": (s["vocab"], s["d"]), "norm_g": (s["d"],),
            "head_w": (s["d"], s["vocab"])}


_GAINS = ("ln1_g", "ln2_g", "q_g", "k_g", "norm_g")


def leaf_draw(cfg, leaf: str):
    """Norm gains around one, the embedding at 1, every matrix at the
    configuration's ``initializer_range`` but the few that
    ``draw_scales`` widens (the configuration's file says which, and why)."""
    if leaf in _GAINS:
        return ("gain", 1.0)
    if leaf == "embed":
        return ("matrix", 1.0)
    return ("matrix", cfg.get("draw_scales", {}).get(
        leaf, cfg.get("initializer_range", 0.02)))


_ATTN = {"q_w": "q_proj", "k_w": "k_proj", "v_w": "v_proj", "o_w": "o_proj",
         "q_g": "q_norm", "k_g": "k_norm"}
_FFN = {"router_w": "gate", "exp_w1": "experts_fc1", "exp_w2": "experts_fc2"}
_BLOCK = {"ln1_g": "input_layernorm", "ln2_g": "post_attention_layernorm"}
_TOP = {"embed": "model.embed_tokens.weight", "norm_g": "model.norm.weight",
        "head_w": "lm_head.weight"}


def parameter_name(leaf: str, layer=None, scanned: bool = False) -> str:
    if leaf in _TOP:
        return _TOP[leaf]
    if scanned:
        raise ValueError("layers of two kinds do not stack")
    if leaf in _BLOCK:
        return f"model.layers.{layer}.{_BLOCK[leaf]}.weight"
    if leaf in _ATTN:
        return f"model.layers.{layer}.self_attn.{_ATTN[leaf]}.weight"
    return f"model.layers.{layer}.mlp.{_FFN[leaf]}.weight"


# -- 3. the equations ---------------------------------------------------------

def inverse_frequencies(cfg, kind: str) -> np.ndarray:
    """float64 [D/2]; a full layer's are YaRN's blend."""
    dim = cfg["head_dim"]
    par = cfg["rope_parameters"][kind]
    theta = float(par["rope_theta"])
    f = theta ** (-np.arange(dim // 2, dtype=np.float64) * 2.0 / dim)
    if par["rope_type"] != "yarn":
        return f

    def turns_dim(beta):
        return dim * math.log(par["original_max_position_embeddings"]
                              / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(par["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(par["beta_slow"])), dim - 1)
    r = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - r) * f + r * f / par["factor"]


def position_tables(seq: int, cfg, yarn: bool = True):
    """{kind: (cos, sin) [seq, D/2]}; ``yarn`` False gives the full layers
    the window layers' plain tables (the omission script's third
    departure)."""
    out = {}
    for kind in (WINDOW, FULL):
        src = kind if yarn else WINDOW
        ang = np.outer(np.arange(seq, dtype=np.float64),
                       inverse_frequencies(cfg, src))
        scale = float(cfg["rope_parameters"][src].get("attention_factor",
                                                      1.0))
        out[kind] = (jnp.asarray(np.cos(ang) * scale, F32),
                     jnp.asarray(np.sin(ang) * scale, F32))
    return out


def embed_tokens(ids, top, cfg):
    return top["embed"][ids]


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, cos, sin):
    """x [S, heads, D]: rotate pairs (i, i + D/2) by the rows' angles."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _block(n: int, want: int) -> int:
    """The largest block of at most ``want`` rows that divides n."""
    b = min(n, want)
    while n % b:
        b -= 1
    return b


def router(es, u, w, s, cfg, keep=None):
    """(chosen [S, k], their gates [S, k]) of rows u; ``keep`` chooses
    fewer than the configuration says (the omission script's)."""
    probs = jax.nn.softmax(es("se,er->sr", u, w["router_w"]), -1)
    picked, chosen = jax.lax.top_k(probs, keep or s["per_tok"])
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    return chosen, picked


def experts(es, u, w, s, cfg, keep=None):
    """``sum_e gate_e SwiGLU_e(u)``: one expert after another over every
    row, at the row's gate for it (0 where the row did not choose it). The
    stacked weights stay in the bfloat16 they were drawn in and are widened
    an expert at a time (the same values)."""
    chosen, gates = router(es, u, w, s, cfg, keep)

    def one(y, xs):
        e, w1, w2 = xs
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
        gp = es("se,ef->sf", u, w1.astype(F32))
        f = gp.shape[-1] // 2
        return y + gate[:, None] * es(
            "sf,fe->se", jax.nn.silu(gp[:, :f]) * gp[:, f:],
            w2.astype(F32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(s["experts"]),
                         w["exp_w1"].astype(jnp.bfloat16),
                         w["exp_w2"].astype(jnp.bfloat16)))
    return y


_QUERY_BLOCK = 128
_EXPERT_BLOCK = 2048


def layer_forward(x, w, tables, cfg, layer, precision="f32",
                  window_mask=True, experts_kept=None):
    """One block over one sequence x [S, d] float32. ``window_mask`` False
    lets a window layer read every row before it, ``experts_kept`` routes
    to fewer experts than the configuration says (the omission script's
    departures)."""
    es = functools.partial(einsum, precision)
    s = sizes(cfg)
    seq, d = x.shape
    eps = cfg["rms_norm_eps"]
    heads, kvh, hd = s["heads"], s["kv"], s["hd"]
    cos, sin = tables[cfg["layer_types"][layer]]
    windowed = is_window(cfg, layer) and window_mask
    big = min(seq, _QUERY_BLOCK)
    pad = -seq % big
    if pad:                 # rows past the end change nothing before them
        x = jnp.pad(x, ((0, pad), (0, 0)))
        cos, sin = (jnp.pad(t, ((0, pad), (0, 0))) for t in (cos, sin))
    total = seq + pad

    h = _rms(x, w["ln1_g"], eps)
    q = _rope(_rms(es("se,ef->sf", h, w["q_w"]).reshape(total, heads, hd),
                   w["q_g"], eps), cos, sin)
    k = _rope(_rms(es("se,ef->sf", h, w["k_w"]).reshape(total, kvh, hd),
                   w["k_g"], eps), cos, sin)
    v = es("se,ef->sf", h, w["v_w"]).reshape(total, kvh, hd)
    # a window layer's block of queries reaches back sliding_window - 1
    # rows before its first: keys are read from a front-padded copy
    back = min(s["window"] - 1, total) if windowed else 0
    if windowed:
        k = jnp.pad(k, ((back, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))
    span = back + big if windowed else total

    def queries(args):
        qb, start = args                                  # [big, H, D]
        qpos = start + jnp.arange(big)
        if windowed:
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, 0)
            kpos = start - back + jnp.arange(span)
            ok = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None]) \
                & (qpos[:, None] - kpos[None, :] < s["window"])
        else:
            kb, vb = k, v
            ok = jnp.arange(span)[None, :] <= qpos[:, None]
        qg = qb.reshape(big, kvh, heads // kvh, hd)
        att = es("sgrd,tgd->grst", qg, kb) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(ok, att, -jnp.inf), -1)
        return es("grst,tgd->sgrd", probs, vb).reshape(big, heads * hd)

    ctx = jax.lax.map(queries, (q.reshape(total // big, big, heads, hd),
                                jnp.arange(0, total, big)))
    x = x + es("sf,fe->se", ctx.reshape(total, heads * hd), w["o_w"])
    u = _rms(x, w["ln2_g"], eps)
    rows = _block(total, _EXPERT_BLOCK)
    ffn = jax.lax.map(lambda ub: experts(es, ub, w, s, cfg, experts_kept),
                      u.reshape(total // rows, rows, d))
    return (x + ffn.reshape(total, d))[:seq]


def head_logits(x, top, cfg, precision="f32"):
    """Final norm and the untied head over rows x [N, d]."""
    return einsum(precision, "ne,ev->nv",
                  _rms(x, top["norm_g"], cfg["rms_norm_eps"]), top["head_w"])


# -- the counts: operations and bytes the equations need ----------------------

def attention_params(cfg) -> int:
    """The four projections of one block."""
    s = sizes(cfg)
    return 2 * s["d"] * s["heads"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    s = sizes(cfg)
    return 3 * s["d"] * s["effn"]


def fixed_matmul_params(cfg) -> int:
    """Weights every decode step multiplies through whatever the routing:
    every block's projections and router, the head. The embedding lookup is
    a gather."""
    s = sizes(cfg)
    return s["layers"] * (attention_params(cfg) + s["d"] * s["experts"]) \
        + s["d"] * s["vocab"]


def kv_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """One token's K and V rows in ONE layer."""
    s = sizes(cfg)
    return 2 * s["kv"] * s["hd"] * itemsize


def layers_of(cfg) -> dict:
    """{"full": n, "window": n} layers of each kind."""
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    return {g: kinds.count(g) for g in _GROUPS}


def kv_rows_read(cfg, sequence_steps: int, context_tokens: int) -> dict:
    """(query, row) pairs the decode steps' attention has to read, by kind,
    all its layers together: a full layer every resident row
    (``context_tokens``: the rows before each decoded token, summed over
    ``sequence_steps`` tokens), a window layer at most ``sliding_window``
    of them a token (taken at the mean context: exact while every
    sequence is on one side of the window)."""
    n = layers_of(cfg)
    return {"full": n["full"] * context_tokens,
            "window": n["window"] * min(
                context_tokens, sequence_steps * cfg["sliding_window"])}


def kv_attention_cost(cfg, rows_read: dict, itemsize: int = 2):
    """(flops, bytes) of the decode steps' attention over ``rows_read``
    (``kv_rows_read``'s): every row's K and V read once; H heads x D x 2
    for the score and for the output."""
    s = sizes(cfg)
    rows = sum(rows_read.values())
    return rows * s["heads"] * s["hd"] * 2 * 2, \
        rows * kv_bytes_per_row(cfg, itemsize)


def decode_step_bytes(cfg, steps: int, experts_touched: int,
                      rows_read: dict, itemsize: int = 2) -> int:
    """Bytes ``steps`` decode steps must read: the fixed weights once a
    step, each touched expert's weights, the K and V of every row read."""
    return itemsize * (steps * fixed_matmul_params(cfg)
                       + experts_touched * expert_params(cfg)) \
        + sum(rows_read.values()) * kv_bytes_per_row(cfg, itemsize)


def routed_experts_cost(cfg, assignments: int, experts_touched: int,
                        itemsize: int = 2):
    """(flops, bytes) of the grouped product: an assignment is a token
    through one expert's three matrices; a touched expert's weights are
    read once a step (or chunk) and layer."""
    return assignments * expert_params(cfg) * 2, \
        experts_touched * expert_params(cfg) * itemsize


def share_of_least(run, executable: str, scopes, least_s: float, note: str,
                   bound: str = "memory"):
    """100 x the least time a call of ``executable`` could take for what
    the window's calls had to do (``least_s``, all of them together) over
    the device time a call took under ``scopes`` (every scope where None);
    None where the trace has nothing. Both sides are taken a call: the
    least over the calls the program counted (the batcher's decode steps,
    the ``serving.prefill_chunk`` spans), the time over the calls the
    device trace holds. They are the same calls while the trace is whole;
    the profiler keeps some two million device events, and this family's
    decode step is some 1,800 of them (a ``fori_loop`` turn a tile of the
    grouped product), so a window of 2,700 steps keeps the first half of
    its device events and a quotient of two window totals would read twice
    what is true."""
    from .. import phases
    a = phases.of_run(run)
    row = a and a["by_executable"].get(executable)
    calls = run.get("decode_steps") if executable == phases.DECODE \
        else a and a["span_counts"].get("serving.prefill_chunk")
    if not row or not row["calls"] or not calls or not run.get("peaks"):
        return None
    seconds = row["seconds"] if scopes is None else sum(
        v for k, v in a["by_scope"].get(executable, {}).items()
        if any(s in k.split("/") for s in scopes))
    if not seconds:
        return None
    run.setdefault("notes", {})[note] = {
        "bound": bound, "seconds_a_call": seconds / row["calls"],
        "least_s_a_call": least_s / calls, "calls_in_trace": row["calls"],
        "calls_counted": calls}
    return 100.0 * (least_s / calls) / (seconds / row["calls"])


def routed_experts_roofline(run, executable: str, phase: str, touched: str):
    """What ``moe_experts_roofline.ide`` (decode steps) and
    ``moe_experts_prefill_roofline.ide`` (chunks) read: the touched experts'
    weights and the assignments' operations of ``phase`` at the chip's peaks
    over ``executable``'s device time under ``experts_routed``, in percent;
    None where the trace or the counter ``touched`` has nothing."""
    from .. import costs
    c = run.get("counters", {})
    if not run.get("peaks") or not c.get(touched):
        return None
    flops, nbytes = routed_experts_cost(
        run["cfg"], c.get(f"moe_assignments_local_{phase}", 0), c[touched])
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    return share_of_least(run, executable, ("experts_routed",), least,
                          f"experts_routed_{phase}", bound)
