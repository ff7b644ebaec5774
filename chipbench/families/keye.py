"""Family ``keye``: the language model of Keye-VL-2.0-30B-A3B (``model_type:
KeyeVL2``): grouped-query attention over per-head keys and values, of which
a DeepSeek-Sparse-Attention indexer (DeepSeek-V3.2-Exp's report and published
inference code; the file's ``sa_config``) keeps ``topk`` rows a query, under
multimodal rotary positions (``rope_scaling.mrope_section``), and a sparse
expert layer in every block. All 48 layers are alike.

Equations, float32, one layer over one sequence x [T, d]; ``RMS`` is RMSNorm
with a gain and ``rms_norm_eps``; D = ``head_dim``, H / KV = the query / key
heads, (n, DI) = ``sa_config``'s (``indexer_num_heads``,
``indexer_head_dim``); ``p[a, t]`` is row t's position on axis a (temporal,
height, width; all three equal for text):

* ``h = RMS(x; g1)``; ``q = h W_q`` (H x D), ``k = h W_k``, ``v = h W_v``
  (KV x D), no bias (``attention_bias`` false).
* (assumed) every head's ``q`` and ``k`` pass ``RMS`` over their D dims with
  one gain for all heads (``g_q``, ``g_k``) before the rotation.
* Rotation over all D dims, pairs ``(i, i + D/2)``, ``[x1 | x2] -> [x1 c - x2
  s | x2 c + x1 s]`` at angle ``p[a(i), t] * theta^(-2i/D)`` made in float64;
  ``a(i)`` by ``mrope_section`` [16, 24, 24]: temporal for i < 16, height for
  16 <= i < 40, width for 40 <= i < 64. ``rope_type`` default: no scaling.
* Indexer: ``qI = h W_Iq`` (n x DI); ``kI = LayerNorm(h W_Ik)`` (DI; gain,
  bias, eps 1e-6); (assumed) the first ``index_rope_dim`` = 32 dims of every
  qI head and of kI rotated by the temporal position, pairs ``(i, i + 16)``,
  angle ``p[0, t] * theta^(-2i/32)``; ``w = h W_Iw * n^-1/2 * DI^-1/2``
  (n). ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``. ``S_t`` is the
  ``topk`` rows ``s <= t`` of largest ``I[t, s]`` (among equal scores the
  lower row first, as ``lax.top_k`` orders them), every row while ``t + 1 <=
  topk``. (assumed) ``q_chunk_size`` and ``kv_chunk_size`` are the published
  kernel's tiles and have no part in the mathematics. **Left out, here and in
  the program alike:** the Hadamard rotation of qI and kI and their float8
  rounding in the published indexer (as in the family ``glm_dsa``).
* ``o[t, j] = sum over s in S_t of softmax_s(q[t, j] . k[s, j // (H / KV)] /
  sqrt(D)) v[s, j // (H / KV)]``, softmax in float32; ``x <- x + concat(o)
  W_o``.
* ``u = RMS(x; g2)``; ``r = softmax(u W_r)`` over all ``num_experts`` in
  float32; the ``num_experts_per_tok`` largest (ties to the lower index);
  gates ``r_e / sum of the chosen r`` (``norm_topk_prob``); ``x <- x + sum_e
  gate_e W2_e (silu(W1g_e u) * W1u_e u)``, width ``moe_intermediate_size``
  (``exp_w1`` holds ``[W1g | W1u]``). No shared expert, no correction bias,
  no dense layer (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty;
  ``intermediate_size`` belongs to no layer).
* Final ``RMS``, untied head over the whole vocabulary.

**Not here:** the vision tower and its projector (the catalog gives "SigLIP-
class ViT 27L" and no sizes) and with them image and video spans, whose
three axes differ; ``position_tables`` takes such positions all the same, and
the CPU tests hold the program to them.

A long sequence fits because nothing is made for all rows and all heads at
once: rows leave K, V and an index key behind, then a block of queries at a
time makes its own q, selects its rows and attends, a key head at a time.
Two savings of work, neither of which changes a number (the cell's
reference follows eight sequences padded to 49,664 rows inside the run's
time limit): a block of queries is given the rows up to the end of its
eighth of the sequence and not those after it, which the causal mask drops
anyway (``_KEY_SPANS``); and an expert multiplies the rows that chose it,
gathered, not every row at a gate of zero (``experts_by_rows``).
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
from .glm_dsa import _layer_norm, selected
from .mellum import (_rms, _rope, embed_tokens,  # noqa: F401
                     head_logits, router, share_of_least)

SPANS = ()
SCOPES = ("qkv_rope", "rope_angles", "indexer", "index_scores", "index_topk",
          "sparse_gather", "sparse_attention", "kv_write", "router",
          "experts_routed")
_PHASED = ("dsa_rows_scored", "dsa_rows_selected", "moe_assignments",
           "moe_assignments_local")
# what this family's readers under chipbench/metrics/ read
COUNTERS = tuple(
    (f"{key}_{phase}", f"serving.{key}_total", {"phase": phase})
    for key in _PHASED for phase in ("decode", "prefill")) + (
    ("moe_experts_touched", "serving.moe_experts_touched_total", {}),
    ("moe_experts_touched_prefill",
     "serving.moe_experts_touched_prefill_total", {}),
    ("moe_expert_tokens_max", "serving.moe_expert_tokens_max", {}),
    ("kv_cache_bytes", "serving.kv_cache_bytes", {"group": "full"}),
    ("index_key_cache_bytes", "serving.index_key_cache_bytes", {}),
)
DISCRETE_CHOICES = ("router_topk", "indexer_topk")
INDEX_ROPE_DIM = 32


# -- sizes --------------------------------------------------------------------

def sizes(cfg) -> dict:
    sa = cfg["sa_config"]
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "in": sa["indexer_num_heads"], "id": sa["indexer_head_dim"],
            "irope": cfg.get("index_rope_dim", INDEX_ROPE_DIM),
            "topk": sa["topk"], "experts": cfg["num_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "effn": cfg["moe_intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


# -- 1. the program's model ---------------------------------------------------

def program_model(cfg: dict, **extra):
    from paddle_tpu.models.keye import KeyeConfig, KeyeForCausalLM
    s = sizes(cfg)
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or cfg["decoder_sparse_step"] != 1 \
            or cfg["mlp_only_layers"] or cfg["num_local_experts"] \
            != s["experts"]:
        raise ValueError("the keye family: one index key a token, every "
                         "layer sparse, every expert held")
    if cfg["rope_scaling"]["rope_type"] != "default":
        raise ValueError("the keye family's rotation is unscaled")
    return KeyeForCausalLM(KeyeConfig(
        vocab_size=s["vocab"], hidden_size=s["d"],
        num_hidden_layers=s["layers"], num_attention_heads=s["heads"],
        num_key_value_heads=s["kv"], head_dim=s["hd"],
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        index_n_heads=s["in"], index_head_dim=s["id"],
        index_rope_dim=s["irope"], index_topk=s["topk"],
        num_experts=s["experts"], num_experts_per_tok=s["per_tok"],
        moe_intermediate_size=s["effn"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg.get("initializer_range", 0.02),
        **{k: cfg[k] for k in ("prefill_key_block",) if k in cfg},
        **extra))


# -- 2. the leaves ------------------------------------------------------------

def layer_kind(cfg, layer: int):
    return "sparse"


def layer_shapes(cfg, layer: int) -> dict:
    s = sizes(cfg)
    d, hd, f = s["d"], s["hd"], s["effn"]
    return {"ln1_g": (d,), "q_w": (d, s["heads"] * hd),
            "k_w": (d, s["kv"] * hd), "v_w": (d, s["kv"] * hd),
            "q_g": (hd,), "k_g": (hd,), "o_w": (s["heads"] * hd, d),
            "iq_w": (d, s["in"] * s["id"]), "ik_w": (d, s["id"]),
            "ik_g": (s["id"],), "ik_b": (s["id"],), "iw_w": (d, s["in"]),
            "ln2_g": (d,), "router_w": (d, s["experts"]),
            "exp_w1": (s["experts"], d, 2 * f),
            "exp_w2": (s["experts"], f, d)}


def top_shapes(cfg) -> dict:
    s = sizes(cfg)
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the keye family's head is untied")
    return {"embed": (s["vocab"], s["d"]), "norm_g": (s["d"],),
            "head_w": (s["d"], s["vocab"])}


_GAINS = ("ln1_g", "ln2_g", "q_g", "k_g", "ik_g", "norm_g")


_RESIDUAL_OUT = ("o_w", "exp_w2")


def leaf_draw(cfg, leaf: str):
    """Norm gains around one, the indexer's key-norm bias at 0.1, the
    embedding at 1, every matrix at the configuration's
    ``initializer_range`` but the two that write into the residual stream
    (``W_o``, every expert's ``W2``), which are drawn at ``initializer_range
    / sqrt(2 x num_hidden_layers)``: the scaled initialisation of GPT-2 and
    Megatron-LM, by the depth as run. Chosen from this reference alone
    (PERF.md section 6, PR 42): with every matrix at ``initializer_range``
    the equations at bfloat16 operands read within 2.3 times of float8 at
    the 99th percentile of the gap, whatever computes them; at these draws
    23 times on the CPU twin (``chipbench/tests/keye_draws.py``) and 13.8
    on the chip, and each omission reads past every limit the cell sets
    between them. Gains
    of 1.5 on the q and k norms (tried first) made the equations
    themselves that sensitive to rounding: their own bfloat16 rounding
    reads 0.85 / 0.096 / 39% there."""
    if leaf in _GAINS:
        return ("gain", 1.0)
    scale = cfg.get("initializer_range", 0.02)
    if leaf in _RESIDUAL_OUT:
        scale /= math.sqrt(2 * cfg["num_hidden_layers"])
    return ("matrix", {"ik_b": 0.1, "embed": 1.0}.get(leaf, scale))


_ATTN = {"q_w": "q_proj", "k_w": "k_proj", "v_w": "v_proj", "o_w": "o_proj",
         "q_g": "q_norm", "k_g": "k_norm", "iq_w": "indexer_wq",
         "ik_w": "indexer_wk", "ik_g": "indexer_k_norm",
         "ik_b": "indexer_k_norm_bias", "iw_w": "indexer_weights_proj"}
_FFN = {"router_w": "gate", "exp_w1": "experts_fc1", "exp_w2": "experts_fc2"}
_BLOCK = {"ln1_g": "input_layernorm", "ln2_g": "post_attention_layernorm"}
_TOP = {"embed": "model.embed_tokens.weight", "norm_g": "model.norm.weight",
        "head_w": "lm_head.weight"}


def parameter_name(leaf: str, layer=None, scanned: bool = False) -> str:
    if leaf in _TOP:
        return _TOP[leaf]
    if scanned:
        raise ValueError("the keye family's layers are not stacked")
    if leaf in _BLOCK:
        return f"model.layers.{layer}.{_BLOCK[leaf]}.weight"
    if leaf in _ATTN:
        return f"model.layers.{layer}.self_attn.{_ATTN[leaf]}.weight"
    return f"model.layers.{layer}.mlp.{_FFN[leaf]}.weight"


# -- 3. the equations ---------------------------------------------------------

def axis_of_pair(cfg) -> np.ndarray:
    """[D/2]: the position axis pair i turns by."""
    section = cfg["rope_scaling"]["mrope_section"]
    if sum(section) != cfg["head_dim"] // 2:
        raise ValueError("mrope_section gives every pair its axis")
    return np.repeat(np.arange(len(section)), section)


def position_tables(seq: int, cfg, positions=None):
    """(cos, sin) [seq, D/2] of the attention's rotation and (cos, sin)
    [seq, 16] of the indexer's. ``positions`` [3, seq]: the rows' (temporal,
    height, width) positions; absent, 0 .. seq on every axis (text)."""
    s = sizes(cfg)
    theta = float(cfg["rope_theta"])
    p = np.tile(np.arange(seq), (3, 1)) if positions is None \
        else np.asarray(positions)
    p = p.astype(np.float64)
    half = s["hd"] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / s["hd"])
    ang = p[axis_of_pair(cfg), :].T * inv[None, :]          # [seq, D/2]
    inv_i = theta ** (-np.arange(s["irope"] // 2, dtype=np.float64) * 2.0
                      / s["irope"])
    ang_i = p[0][:, None] * inv_i[None, :]
    return tuple(jnp.asarray(f(a), F32) for a in (ang, ang_i)
                 for f in (np.cos, np.sin))


def _rope_first(x, cos, sin):
    """x [S, heads, W]: the first 2 x cos' width dims rotated, pairs (i, i +
    that width)."""
    rope = 2 * cos.shape[1]
    return jnp.concatenate([_rope(x[..., :rope], cos, sin), x[..., rope:]],
                           -1)


_QUERY_BLOCK = 128
_KEY_SPANS = 8
_EXPERT_ROOM = 2


def kth_largest(x, k: int):
    """The k-th largest of every row of x [Q, T] float32 (no NaN), exactly:
    the floats' order is that of their bits read as sign and magnitude, so
    the answer's 32 bits are settled one at a time from the top, each by a
    count of the row's values at or over a candidate."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31)), jnp.uint32)

    def settle(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= trial[:, None], -1) >= k
        return jnp.where(enough, trial, found)

    found = jax.lax.fori_loop(0, 32, settle,
                              jnp.zeros(x.shape[0], jnp.uint32))
    back = jax.lax.bitcast_convert_type(found, jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.where(back < 0, back & jnp.int32(2 ** 31 - 1), ~back), F32)


def kept_rows(scores, qpos, topk: int, mode: str = "indexer"):
    """S_t as a mask [Q, T]: the family ``glm_dsa``'s ``selected`` with the
    k-th largest score found without a sort (over 49,664 rows
    ``lax.top_k`` is 1.6 s of a sample and layer's 3.2, the counts 0.4:
    PERF.md section 6, PR 42)."""
    if mode != "indexer" or topk >= scores.shape[1]:
        return selected(scores, qpos, topk, mode)
    kpos = jnp.arange(scores.shape[1])
    causal = kpos[None, :] <= qpos[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = kth_largest(masked, topk)[:, None]
    above = masked > kth
    tie = causal & (masked == kth)          # the lower rows of a tie first
    need = topk - jnp.sum(above, -1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, -1) <= need))


def experts_by_rows(es, u, w, s, cfg, keep=None):
    """``sum_e gate_e SwiGLU_e(u)`` over rows u [S, d], one expert after
    another, each over the rows that chose it alone: they are gathered (in
    row order, ``jnp.nonzero`` at a fixed size), multiplied and added back
    at the row's gate. The room is ``_EXPERT_ROOM`` times an expert's even
    share of the rows; an expert that more rows chose than that multiplies
    every row at its gate instead, 0 where the row did not choose it (what
    the family ``mellum``'s ``experts`` does for every expert), so the sum
    is the equations' whatever the routing. The stacked weights stay in the
    bfloat16 they were drawn in and are widened an expert at a time."""
    chosen, gates = router(es, u, w, s, cfg, keep)
    rows = u.shape[0]
    room = min(rows, -(-_EXPERT_ROOM * rows * chosen.shape[1]
                       // s["experts"]))

    def swiglu(x, w1, w2):
        gp = es("se,ef->sf", x, w1.astype(F32))
        f = gp.shape[-1] // 2
        return es("sf,fe->se", jax.nn.silu(gp[:, :f]) * gp[:, f:],
                  w2.astype(F32))

    def one(y, xs):
        e, w1, w2 = xs
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
        mine = jnp.any(chosen == e, -1)

        def gathered(y):
            at = jnp.nonzero(mine, size=room, fill_value=rows)[0]
            out = swiglu(u.at[at].get(mode="fill", fill_value=0.0), w1, w2)
            return y.at[at].add(
                gate.at[at].get(mode="fill", fill_value=0.0)[:, None] * out,
                mode="drop", indices_are_sorted=True, unique_indices=True)

        return jax.lax.cond(jnp.sum(mine) > room,
                            lambda y: y + gate[:, None] * swiglu(u, w1, w2),
                            gathered, y), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(s["experts"]),
                         w["exp_w1"].astype(jnp.bfloat16),
                         w["exp_w2"].astype(jnp.bfloat16)))
    return y


def layer_forward(x, w, tables, cfg, layer, precision="f32",
                  selection="indexer", experts_kept=None,
                  return_selection=False):
    """One block over one sequence x [S, d] float32. ``selection`` "newest"
    or "all" replaces the indexer's rows, ``experts_kept`` routes to fewer
    experts than the configuration says (the omission script's
    departures)."""
    es = functools.partial(einsum, precision)
    s = sizes(cfg)
    seq, d = x.shape
    eps = cfg["rms_norm_eps"]
    heads, kvh, hd = s["heads"], s["kv"], s["hd"]
    cos, sin, icos, isin = tables
    big = min(seq, _QUERY_BLOCK)
    pad = -seq % big
    if pad:                 # rows past the end change nothing before them
        x = jnp.pad(x, ((0, pad), (0, 0)))
        cos, sin, icos, isin = (jnp.pad(t, ((0, pad), (0, 0)))
                                for t in (cos, sin, icos, isin))
    total = seq + pad

    # what every row leaves behind for later queries: K, V, the index key
    h = _rms(x, w["ln1_g"], eps)
    k = _rope(_rms(es("se,ef->sf", h, w["k_w"]).reshape(total, kvh, hd),
                   w["k_g"], eps), cos, sin)
    v = es("se,ef->sf", h, w["v_w"]).reshape(total, kvh, hd)
    k_i = _rope_first(_layer_norm(es("se,ed->sd", h, w["ik_w"]), w["ik_g"],
                                  w["ik_b"])[:, None, :], icos, isin)[:, 0]
    k_g = jnp.moveaxis(k, 1, 0)                           # [KV, T, D]
    v_g = jnp.moveaxis(v, 1, 0)

    def queries(k_i, k_g, v_g, args):
        """A block of ``big`` queries over the rows given (all that are not
        after it, at the least): their q and index queries, the rows they
        keep, attention a key head at a time, the output product."""
        xb, cb, sb, icb, isb, start = args
        hb = _rms(xb, w["ln1_g"], eps)
        qb = _rope(_rms(es("se,ef->sf", hb, w["q_w"]).reshape(
            big, heads, hd), w["q_g"], eps), cb, sb)
        qib = _rope_first(es("se,ef->sf", hb, w["iq_w"]).reshape(
            big, s["in"], s["id"]), icb, isb)
        wib = es("se,en->sn", hb, w["iw_w"]) * s["in"] ** -0.5 \
            * s["id"] ** -0.5
        qpos = start + jnp.arange(big)
        scores = jnp.sum(jax.nn.relu(es("qnd,td->qnt", qib, k_i))
                         * wib[:, :, None], 1)            # [big, T]
        keep = kept_rows(scores, qpos, s["topk"], selection)
        qg = jnp.moveaxis(qb.reshape(big, kvh, heads // kvh, hd), 1, 0)

        def key_head(a):
            qh, kh, vh = a                  # [big, rep, D], [T, D], [T, D]
            att = es("qrd,td->rqt", qh, kh) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(keep[None], att, -jnp.inf), -1)
            return es("rqt,td->qrd", probs, vh)

        ctx = jax.lax.map(key_head, (qg, k_g, v_g))       # [KV, big, rep, D]
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(big, heads * hd)
        out = xb + es("sf,fe->se", ctx, w["o_w"])
        if return_selection:
            return out, jnp.pad(keep, ((0, 0), (0, total - keep.shape[1])))
        return out

    n_big = total // big

    def split(a):
        return a.reshape((n_big, big) + a.shape[1:])

    blocks = (split(x), split(cos), split(sin), split(icos), split(isin),
              jnp.arange(0, total, big))
    per = -(-n_big // _KEY_SPANS)       # blocks of queries a span of rows
    out = []
    for first in range(0, n_big, per):
        last = min(first + per, n_big)
        end = last * big                # no query of these sees a later row
        out.append(jax.lax.map(
            functools.partial(queries, k_i[:end], k_g[:, :end],
                              v_g[:, :end]),
            tuple(b[first:last] for b in blocks)))
    out = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *out)
    x = (out[0] if return_selection else out).reshape(total, d)
    x = (x + experts_by_rows(es, _rms(x, w["ln2_g"], eps), w, s, cfg,
                             experts_kept))[:seq]
    if return_selection:
        return x, out[1].reshape(total, total)[:seq, :seq]
    return x


# -- the counts: operations and bytes the equations need ----------------------

def attention_params(cfg) -> int:
    """The four projections and the indexer's three of one block."""
    s = sizes(cfg)
    return 2 * s["d"] * s["heads"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"] \
        + s["d"] * (s["in"] * s["id"] + s["id"] + s["in"])


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    s = sizes(cfg)
    return 3 * s["d"] * s["effn"]


def fixed_matmul_params(cfg) -> int:
    """Weights every decode step multiplies through whatever the routing:
    every block's projections, indexer and router, the head. The embedding
    lookup is a gather."""
    s = sizes(cfg)
    return s["layers"] * (attention_params(cfg) + s["d"] * s["experts"]) \
        + s["d"] * s["vocab"]


def kv_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """One token's K and V rows in ONE layer."""
    s = sizes(cfg)
    return 2 * s["kv"] * s["hd"] * itemsize


def index_key_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """One token's index key in ONE layer, as the mathematics requires it
    (the program holds it whole lanes wide: twice that)."""
    return sizes(cfg)["id"] * itemsize


def cache_bytes_per_row(cfg, itemsize: int = 2) -> int:
    """What one token has to hold, all layers."""
    return cfg["num_hidden_layers"] * (kv_bytes_per_row(cfg, itemsize)
                                       + index_key_bytes_per_row(cfg,
                                                                 itemsize))


def decode_step_bytes(cfg, steps: int, experts_touched: int,
                      rows_scored: int, rows_selected: int,
                      itemsize: int = 2) -> int:
    """Bytes ``steps`` decode steps must read: the fixed weights once a
    step, each touched expert's weights, the index key of every row scored
    and K and V of every row kept (the counters sum both over layers)."""
    return itemsize * (steps * fixed_matmul_params(cfg)
                       + experts_touched * expert_params(cfg)) \
        + rows_scored * index_key_bytes_per_row(cfg, itemsize) \
        + rows_selected * kv_bytes_per_row(cfg, itemsize)


def indexer_cost(cfg, queries: int, rows_scored: int, itemsize: int = 2):
    """(flops, bytes) of scoring: ``rows_scored`` (query, row) pairs of one
    layer cost n heads x DI x 2 each; read are the index keys of the rows a
    call scores (``rows_scored / queries`` a block of ``queries``, once)."""
    s = sizes(cfg)
    return rows_scored * s["in"] * s["id"] * 2, \
        rows_scored * s["id"] * itemsize / queries


def sparse_attention_cost(cfg, rows_selected: int, itemsize: int = 2):
    """(flops, bytes) of the decode steps' attention over ``rows_selected``
    (query, row) pairs: H heads x D x 2 for the score and for the output;
    every kept row's K and V read once."""
    s = sizes(cfg)
    return rows_selected * s["heads"] * s["hd"] * 2 * 2, \
        rows_selected * kv_bytes_per_row(cfg, itemsize)


def routed_experts_cost(cfg, assignments: int, experts_touched: int,
                        itemsize: int = 2):
    """(flops, bytes) of the grouped product: an assignment is a token
    through one expert's three matrices; a touched expert's weights are
    read once a step (or chunk) and layer."""
    return assignments * expert_params(cfg) * 2, \
        experts_touched * expert_params(cfg) * itemsize


def roofline_share(run, executable: str, scopes, flops, nbytes, note: str):
    """100 x the least time the window's calls of ``executable`` could take
    for ``flops`` and ``nbytes`` at the chip's peaks over their device time
    under ``scopes``, both sides a call (``share_of_least``: the trace may
    be cut); None where the trace has nothing."""
    from .. import costs
    if not run.get("peaks"):
        return None
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    return share_of_least(run, executable, scopes, least, note, bound)


def routed_experts_roofline(run, executable: str, phase: str, touched: str):
    """What ``moe_experts_roofline.longctx`` (decode steps) and
    ``moe_experts_prefill_roofline.longctx`` (chunks) read: the touched
    experts' weights and the assignments' operations of ``phase`` over
    ``executable``'s device time under ``experts_routed``, in percent."""
    c = run.get("counters", {})
    if not c.get(touched):
        return None
    return roofline_share(
        run, executable, ("experts_routed",),
        *routed_experts_cost(run["cfg"],
                             c.get(f"moe_assignments_local_{phase}", 0),
                             c[touched]), f"experts_routed_{phase}")
