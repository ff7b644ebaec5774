"""Family ``glm_dsa``: GLM-5's decoder (``model_type: glm_moe_dsa``), which is
DeepSeek-V3's latent attention (MLA) and routed experts (arXiv:2412.19437)
with DeepSeek-V3.2's sparse-attention indexer beside the attention
(DeepSeek-V3.2-Exp's report and published inference code).

Equations, for one sequence x [S, d]; ``RMS`` is RMSNorm with a gain and
``rms_norm_eps``; a block is ``x += Attn(RMS(x)); x += FFN(RMS'(x))``:

* MLA, H heads. With ``h = RMS(x)``: ``cq = RMS(h W_qa)`` (q_lora_rank);
  ``q = cq W_qb`` -> H x (nope + rope) = ``[q_nope | q_pe]``; ``[ckv |
  k_pe] = h W_kva`` (kv_lora_rank | rope), ``ckv = RMS(ckv)``; RoPE with
  ``rope_theta`` on interleaved pairs (2i, 2i+1) of ``q_pe`` and of the one
  ``k_pe`` all heads share; ``[k_nope | v]`` of head i ``= ckv W_kvb`` (nope
  | v_head_dim). ``score_i[t, s] = (q_nope . k_nope + q_pe . k_pe) /
  sqrt(nope + rope)``; softmax in float32 over ``s in S_t``; the heads'
  outputs side by side times ``W_o``.
* Indexer, n heads of D: ``qI = cq W_Iq`` (n x D); ``kI = LayerNorm(h W_Ik)``
  (D; gain, bias, eps 1e-6); RoPE as above on the FIRST rope dims of every
  qI head and of kI; ``w = h W_Iw / sqrt(n D)`` (n). ``I[t, s] = sum_j w[t,
  j] relu(qI[t, j] . kI[s])``. ``S_t`` is the index_topk rows ``s <= t`` of
  largest ``I[t, s]`` (among equal scores the lower row first, as
  ``lax.top_k`` orders them), every row while ``t + 1 <= index_topk``.
* FFN. Blocks before ``first_k_dense_replace``: ``SwiGLU(u) = (silu(g) *
  p) W_2``, ``[g | p] = u W_1``, width intermediate_size. Later blocks:
  ``s = sigmoid(u W_r)`` (n_routed_experts wide, float32); the
  num_experts_per_tok chosen are the largest of ``s + b`` (``b`` is used
  for the choice alone); ``g_e = routed_scaling_factor * s_e / sum of the
  chosen s``; ``y = sum over chosen e of g_e SwiGLU_e(u) + SwiGLU_shared(u)``,
  widths moe_intermediate_size. **This chip's share:** the configuration
  holds experts ``experts_held_start .. + n_routed_experts`` (its
  ``n_routed_experts`` counts the experts HELD; ``router_width`` is the
  published count the router scores); a chosen expert held elsewhere adds
  nothing, here and in the program alike.
* head: final RMS, ``logits = x W_head`` (untied) over the vocabulary held.

A long sequence fits because nothing is made for all rows and all heads at
once: rows leave a latent row and an index key behind (phase A), then
blocks of queries select their rows and attend, a group of heads at a time
(phase B), and the FFN runs on the block.

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

SPANS = ()
SCOPES = ("mla_q", "mla_latent", "latent_write", "indexer", "index_scores",
          "index_topk", "sparse_gather", "sparse_attention", "router",
          "experts_routed", "expert_shared")
_PHASED = ("dsa_rows_scored", "dsa_rows_selected", "moe_assignments",
           "moe_assignments_local")
COUNTERS = tuple(
    (f"{key}_{phase}", f"serving.{key}_total", {"phase": phase})
    for key in _PHASED for phase in ("decode", "prefill")) + (
    ("moe_experts_touched", "serving.moe_experts_touched_total", {}),
    ("moe_expert_tokens_max", "serving.moe_expert_tokens_max", {}),
    ("latent_cache_bytes", "serving.latent_cache_bytes", {}),
)
DISCRETE_CHOICES = ("router_topk", "indexer_topk")


# -- sizes --------------------------------------------------------------------

def sizes(cfg) -> dict:
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "q_lora": cfg["q_lora_rank"], "lora": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "in": cfg["index_n_heads"],
        "id": cfg["index_head_dim"], "topk": cfg["index_topk"],
        "ffn": cfg["intermediate_size"], "effn": cfg["moe_intermediate_size"],
        "held": cfg["n_routed_experts"],
        "held_start": cfg.get("experts_held_start", 0),
        "router": cfg.get("router_width", cfg["n_routed_experts"]),
        "per_tok": cfg["num_experts_per_tok"],
        "shared": cfg.get("n_shared_experts", 1),
        "dense": cfg["first_k_dense_replace"],
        "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def is_moe(cfg, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


# -- 1. the program's model ---------------------------------------------------

def program_model(cfg: dict, **extra):
    from paddle_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaForCausalLM
    s = sizes(cfg)
    return GlmDsaForCausalLM(GlmDsaConfig(
        vocab_size=s["vocab"], hidden_size=s["d"],
        intermediate_size=s["ffn"], moe_intermediate_size=s["effn"],
        num_hidden_layers=s["layers"], first_k_dense_replace=s["dense"],
        num_attention_heads=s["heads"], q_lora_rank=s["q_lora"],
        kv_lora_rank=s["lora"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["v"],
        index_n_heads=s["in"], index_head_dim=s["id"],
        index_topk=s["topk"], n_routed_experts=s["router"],
        num_experts_per_tok=s["per_tok"], n_shared_experts=s["shared"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held_start=s["held_start"], experts_held_count=s["held"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg.get("initializer_range", 0.02),
        **{k: cfg[k] for k in ("prefill_key_block",) if k in cfg},
        **extra))


# -- 2. the leaves ------------------------------------------------------------

def layer_kind(cfg, layer: int):
    return "expert" if is_moe(cfg, layer) else "dense"


def layer_shapes(cfg, layer: int) -> dict:
    s = sizes(cfg)
    d, heads = s["d"], s["heads"]
    shapes = {
        "ln1_g": (d,), "q_a_w": (d, s["q_lora"]), "q_a_g": (s["q_lora"],),
        "q_b_w": (s["q_lora"], heads * (s["nope"] + s["rope"])),
        "kv_a_w": (d, s["lora"] + s["rope"]), "kv_a_g": (s["lora"],),
        "kv_b_w": (s["lora"], heads * (s["nope"] + s["v"])),
        "o_w": (heads * s["v"], d),
        "iq_w": (s["q_lora"], s["in"] * s["id"]), "ik_w": (d, s["id"]),
        "ik_g": (s["id"],), "ik_b": (s["id"],), "iw_w": (d, s["in"]),
        "ln2_g": (d,)}
    if is_moe(cfg, layer):
        f = s["effn"]
        shapes.update({
            "router_w": (d, s["router"]), "router_b": (s["router"],),
            "exp_w1": (s["held"], d, 2 * f), "exp_w2": (s["held"], f, d),
            "sh_w1": (d, 2 * f * s["shared"]),
            "sh_w2": (f * s["shared"], d)})
    else:
        shapes.update({"mlp_w1": (d, 2 * s["ffn"]),
                       "mlp_w2": (s["ffn"], d)})
    return shapes


def top_shapes(cfg) -> dict:
    s = sizes(cfg)
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the glm_dsa family's head is untied")
    return {"embed": (s["vocab"], s["d"]), "norm_g": (s["d"],),
            "head_w": (s["d"], s["vocab"])}


_GAINS = ("ln1_g", "ln2_g", "q_a_g", "kv_a_g", "ik_g", "norm_g")
def leaf_draw(cfg, leaf: str):
    """Norm gains around one; the indexer's key-norm bias and the router's
    correction bias at 0.1; the embedding at 1; every matrix at the
    configuration's ``initializer_range``. Nothing is drawn wider to make
    a mechanism count for more: at these scales the newest rows in the
    indexer's place, or the routed branch left out, read past every limit
    of the cell, and wider draws (tried on the chip: PERF.md section 6)
    make every choice that rounding flips count for more too."""
    if leaf in _GAINS:
        return ("gain", 1.0)
    if leaf in ("ik_b", "router_b"):
        return ("matrix", 0.1)
    if leaf == "embed":
        return ("matrix", 1.0)
    return ("matrix", cfg.get("initializer_range", 0.02))


_ATTN = {"q_a_w": "q_a_proj", "q_a_g": "q_a_layernorm", "q_b_w": "q_b_proj",
         "kv_a_w": "kv_a_proj_with_mqa", "kv_a_g": "kv_a_layernorm",
         "kv_b_w": "kv_b_proj", "o_w": "o_proj", "iq_w": "indexer_wq_b",
         "ik_w": "indexer_wk", "ik_g": "indexer_k_norm",
         "ik_b": "indexer_k_norm_bias", "iw_w": "indexer_weights_proj"}
_FFN = {"mlp_w1": "fc1", "mlp_w2": "fc2", "router_w": "gate",
        "router_b": "e_score_correction_bias", "exp_w1": "experts_fc1",
        "exp_w2": "experts_fc2", "sh_w1": "shared_experts.fc1",
        "sh_w2": "shared_experts.fc2"}
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

def position_tables(seq: int, cfg):
    rope = cfg["qk_rope_head_dim"]
    inv = 1.0 / (float(cfg["rope_parameters"]["rope_theta"])
                 ** (np.arange(0, rope, 2, dtype=np.float64) / rope))
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def embed_tokens(ids, top, cfg):
    return top["embed"][ids]


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _layer_norm(x, gain, bias, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def _rope(x, cos, sin):
    """x [S, ..., R]: rotate pairs (2i, 2i+1) by the rows' angles [S, R/2]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _rope_first(x, cos, sin, rope):
    return jnp.concatenate([_rope(x[..., :rope], cos, sin), x[..., rope:]],
                           -1)


def _block(n: int, want: int) -> int:
    """The largest block of at most ``want`` rows that divides n."""
    b = min(n, want)
    while n % b:
        b -= 1
    return b


def swiglu(es, u, w1, w2):
    gp = es("se,ef->sf", u, w1)
    f = gp.shape[-1] // 2
    return es("sf,fe->se", jax.nn.silu(gp[:, :f]) * gp[:, f:], w2)


def router(es, u, w, s, cfg):
    """(chosen [S, k], their gates [S, k]) of rows u."""
    score = jax.nn.sigmoid(es("se,er->sr", u, w["router_w"]))
    _, chosen = jax.lax.top_k(score + w["router_b"], s["per_tok"])
    picked = jnp.take_along_axis(score, chosen, -1)
    return chosen, cfg["routed_scaling_factor"] * picked \
        / jnp.sum(picked, -1, keepdims=True)


def routed(es, u, w, s, cfg, held=None):
    """The part of ``sum_e g_e SwiGLU_e(u)`` that the experts ``held`` (start,
    count; the configuration's own where None) give: one held expert after
    another over every row, at the row's gate for it (0 where the row did
    not choose it). The stacked weights stay in the bfloat16 they were
    drawn in and are widened an expert at a time (the same values: sixteen
    experts in float32 at once are 3.3 GB beside a long sequence)."""
    start, count = held or (s["held_start"], s["held"])
    chosen, gates = router(es, u, w, s, cfg)

    def one(y, xs):
        e, w1, w2 = xs
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
        return y + gate[:, None] * swiglu(es, u, w1.astype(F32),
                                          w2.astype(F32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (start + jnp.arange(count),
                         w["exp_w1"][:count].astype(jnp.bfloat16),
                         w["exp_w2"][:count].astype(jnp.bfloat16)))
    return y


def ffn(es, x, w, s, cfg, layer):
    u = _rms(x, w["ln2_g"], cfg["rms_norm_eps"])
    if not is_moe(cfg, layer):
        return x + swiglu(es, u, w["mlp_w1"], w["mlp_w2"])
    return x + routed(es, u, w, s, cfg) + swiglu(es, u, w["sh_w1"],
                                                 w["sh_w2"])


def rows_left_behind(es, x, w, cos, sin, s, eps):
    """What every row leaves for later queries: (latent [S, lora + rope]
    after norm and RoPE, index key [S, D])."""
    h = _rms(x, w["ln1_g"], eps)
    kv = es("se,ec->sc", h, w["kv_a_w"])
    latent = jnp.concatenate([_rms(kv[:, :s["lora"]], w["kv_a_g"], eps),
                              _rope(kv[:, s["lora"]:], cos, sin)], -1)
    k_i = _rope_first(_layer_norm(es("se,ed->sd", h, w["ik_w"]), w["ik_g"],
                                  w["ik_b"]), cos, sin, s["rope"])
    return latent, k_i


def index_scores(es, h, cq, k_i, w, cos, sin, s):
    """I[t, s] for queries (h, cq) [Q, .] against keys k_i [T, D]."""
    q_i = _rope_first(es("sc,cf->sf", cq, w["iq_w"]).reshape(
        h.shape[0], s["in"], s["id"]), cos, sin, s["rope"])
    w_i = es("se,en->sn", h, w["iw_w"]) * (s["in"] * s["id"]) ** -0.5
    return jnp.sum(jax.nn.relu(es("qnd,td->qnt", q_i, k_i))
                   * w_i[:, :, None], 1)


def selected(scores, qpos, topk: int, mode: str = "indexer"):
    """S_t as a mask [Q, T]. ``mode``: "indexer" the equations'; "newest"
    keeps the newest index_topk rows instead and "all" every row (the
    omission tests' two departures)."""
    kpos = jnp.arange(scores.shape[1])
    causal = kpos[None, :] <= qpos[:, None]
    if mode == "all" or topk >= scores.shape[1]:
        return causal
    if mode == "newest":
        return causal & (qpos[:, None] - kpos[None, :] < topk)
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, topk)[0][:, -1:]
    above = masked > kth
    tie = causal & (masked == kth)          # the lower rows of a tie first
    need = topk - jnp.sum(above, -1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, -1) <= need))


_HEAD_GROUP = 8
_QUERY_BLOCK = 128
_ROW_BLOCK = 2048


def layer_forward(x, w, tables, cfg, layer, precision="f32",
                  selection="indexer", with_routed=True,
                  return_selection=False):
    """One block over one sequence x [S, d] float32."""
    es = functools.partial(einsum, precision)
    s = sizes(cfg)
    seq, d = x.shape
    eps = cfg["rms_norm_eps"]
    heads, nope, rope, lora, v = s["heads"], s["nope"], s["rope"], \
        s["lora"], s["v"]
    big = min(seq, _ROW_BLOCK)
    pad = -seq % big
    cos, sin = tables
    if pad:                 # rows past the end change nothing before them
        x = jnp.pad(x, ((0, pad), (0, 0)))
        cos, sin = (jnp.pad(t, ((0, pad), (0, 0))) for t in (cos, sin))
    total = seq + pad
    n_big = total // big

    def split(a):
        return a.reshape((n_big, big) + a.shape[1:])

    # phase A: what every row leaves behind
    latent, k_i = jax.lax.map(
        lambda a: rows_left_behind(es, a[0], w, a[1], a[2], s, eps),
        (split(x), split(cos), split(sin)))
    latent = latent.reshape(total, -1)
    k_i = k_i.reshape(total, -1)
    group = _block(heads, _HEAD_GROUP)
    w_kvb = w["kv_b_w"].reshape(lora, heads // group, group, nope + v)
    w_kvb = jnp.moveaxis(w_kvb, 1, 0)              # [G, lora, group, .]
    w_qb = jnp.moveaxis(w["q_b_w"].reshape(
        -1, heads // group, group, nope + rope), 1, 0)
    w_o = w["o_w"].reshape(heads // group, group * v, d)
    small = _block(big, _QUERY_BLOCK)

    def queries(args):
        """A block of ``big`` queries: select, attend, FFN."""
        xb, cb, sb, qpos = args
        h = _rms(xb, w["ln1_g"], eps)
        cq = _rms(es("se,ec->sc", h, w["q_a_w"]), w["q_a_g"], eps)

        def select(a):
            hs, cqs, cs, ss, pos = a
            return selected(index_scores(es, hs, cqs, k_i, w, cs, ss, s),
                            pos, s["topk"], selection)

        def sub(a):
            return a.reshape((big // small, small) + a.shape[1:])

        keep = jax.lax.map(select, (sub(h), sub(cq), sub(cb), sub(sb),
                                    sub(qpos))).reshape(big, total)

        def head_group(out, ws):
            wq, wkv, wo = ws
            q = es("sc,cgf->sgf", cq, wq)
            q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cb, sb)
            kv = es("tc,cgf->tgf", latent[:, :lora], wkv)
            k_nope, val = kv[..., :nope], kv[..., nope:]

            def attend(a):
                qn, qp, ok = a
                att = (es("qgd,tgd->gqt", qn, k_nope)
                       + es("qgr,tr->gqt", qp, latent[:, lora:])) \
                    / math.sqrt(nope + rope)
                probs = jax.nn.softmax(jnp.where(ok[None], att, -jnp.inf),
                                       -1)
                return es("gqt,tgv->qgv", probs, val).reshape(small, -1)

            ctx = jax.lax.map(attend, (sub(q_nope), sub(q_pe), sub(keep)))
            return out + es("sf,fe->se", ctx.reshape(big, -1), wo), None

        attn, _ = jax.lax.scan(head_group, jnp.zeros_like(xb),
                               (w_qb, w_kvb, w_o))
        xb = xb + attn
        if not with_routed and is_moe(cfg, layer):
            u = _rms(xb, w["ln2_g"], eps)
            xb = xb + swiglu(es, u, w["sh_w1"], w["sh_w2"])
        else:
            xb = ffn(es, xb, w, s, cfg, layer)
        return (xb, keep) if return_selection else xb

    out = jax.lax.map(queries, (split(x), split(cos), split(sin),
                                split(jnp.arange(total))))
    if return_selection:
        return out[0].reshape(total, d)[:seq], \
            out[1].reshape(total, total)[:seq, :seq]
    return out.reshape(total, d)[:seq]


def head_logits(x, top, cfg, precision="f32"):
    """Final norm and the untied head over rows x [N, d]."""
    return einsum(precision, "ne,ev->nv",
                  _rms(x, top["norm_g"], cfg["rms_norm_eps"]), top["head_w"])


# -- the counts: operations and bytes the equations need ----------------------

def attention_params(cfg) -> int:
    """MLA's and the indexer's matrices of one block."""
    s = sizes(cfg)
    d, heads = s["d"], s["heads"]
    return d * s["q_lora"] + s["q_lora"] * heads * (s["nope"] + s["rope"]) \
        + d * (s["lora"] + s["rope"]) \
        + s["lora"] * heads * (s["nope"] + s["v"]) + heads * s["v"] * d \
        + s["q_lora"] * s["in"] * s["id"] + d * s["id"] + d * s["in"]


def expert_params(cfg) -> int:
    """One routed expert's matrices."""
    s = sizes(cfg)
    return 3 * s["d"] * s["effn"]


def fixed_matmul_params(cfg) -> int:
    """Weights every decode step multiplies through whatever the routing:
    attention and indexer of every block, the dense blocks' SwiGLU, every
    router and shared expert, the head. The embedding lookup is a gather."""
    s = sizes(cfg)
    moe = s["layers"] - s["dense"]
    return s["layers"] * attention_params(cfg) \
        + s["dense"] * 3 * s["d"] * s["ffn"] \
        + moe * (s["d"] * s["router"] + s["shared"] * expert_params(cfg)) \
        + s["d"] * s["vocab"]


def latent_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """One token's latent row in ONE layer."""
    s = sizes(cfg)
    return (s["lora"] + s["rope"]) * itemsize


def index_key_bytes_per_row(cfg, itemsize: int = 2) -> int:
    return cfg["index_head_dim"] * itemsize


def cache_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """What one token holds in the pools, all layers."""
    return cfg["num_hidden_layers"] * (latent_bytes_per_row(cfg, itemsize)
                                       + index_key_bytes_per_row(cfg,
                                                                 itemsize))


def decode_step_bytes(cfg, steps: int, experts_touched: int,
                      rows_scored: int, rows_selected: int,
                      itemsize: int = 2) -> int:
    """Bytes ``steps`` decode steps must read: the fixed weights once a
    step, each touched expert's weights, the index key of every row scored
    and the latent row of every row selected (the counters sum both over
    layers)."""
    return itemsize * (steps * fixed_matmul_params(cfg)
                       + experts_touched * expert_params(cfg)) \
        + rows_scored * index_key_bytes_per_row(cfg, itemsize) \
        + rows_selected * latent_bytes_per_row(cfg, itemsize)


def indexer_cost(cfg, queries: int, rows_scored: int, itemsize: int = 2):
    """(flops, bytes) of scoring: ``rows_scored`` (query, row) pairs of one
    layer cost n heads x D x 2 each; read are the index keys of the rows a
    call scores (``rows_scored / queries`` a query block, once) and the
    queries."""
    s = sizes(cfg)
    flops = rows_scored * s["in"] * s["id"] * 2
    nbytes = rows_scored * s["id"] * itemsize
    return flops, nbytes


def sparse_attention_cost(cfg, rows_selected: int, itemsize: int = 2):
    """(flops, bytes) of the absorbed attention of decode steps over
    ``rows_selected`` (query, row) pairs: H heads x (lora + rope) for the
    score and x lora for the output, 2 each; every selected latent row
    read."""
    s = sizes(cfg)
    flops = rows_selected * s["heads"] * (2 * s["lora"] + s["rope"]) * 2
    return flops, rows_selected * latent_bytes_per_row(cfg, itemsize)


def routed_experts_cost(cfg, assignments: int, experts_touched: int,
                        itemsize: int = 2):
    """(flops, bytes) of the grouped product: an assignment is a token
    through one expert's three matrices; a touched expert's weights are
    read once a step and layer."""
    return assignments * expert_params(cfg) * 2, \
        experts_touched * expert_params(cfg) * itemsize
