"""Operations and bytes that the algorithm needs, from shapes alone.

These are the numerators of every MFU, MBU and roofline share the benchmark
reports. They count what the mathematics requires: causal attention is half a
square, recomputed work is not counted, the embedding lookup is not counted.
``cfg`` is a configuration file's dict (the published keys).
"""
from __future__ import annotations


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg) -> int:
    """Weights of one decoder layer that a token is multiplied through."""
    hs, d = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = hs * h * d + 2 * hs * kv * d + h * d * hs
    return attn + 3 * hs * cfg["intermediate_size"]


def matmul_params(cfg) -> int:
    """All layers plus the output head (tied or not, a token is multiplied
    through it); the embedding lookup is a gather and counts nothing."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg) -> int:
    hs, v = cfg["hidden_size"], cfg["vocab_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * hs
    embed = hs * v * (1 if cfg.get("tie_word_embeddings") else 2)
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + embed + norms


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward of one token in a causal sequence of ``seq``:
    6 per matmul weight, plus attention's two products over half the square
    (2 * 2 * seq/2 * hidden forward, twice that backward)."""
    attn = 6 * cfg["num_hidden_layers"] * seq * cfg["num_attention_heads"] \
        * head_dim(cfg)
    return 6.0 * matmul_params(cfg) + attn


def flash_fwd_cost(cfg, batch: int, seq: int, itemsize: int = 2):
    """(flops, bytes) of ONE causal flash forward call over [batch, seq]:
    QK^T and PV over the lower triangle; Q, K, V read and O written once."""
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    flops = 2 * 2 * batch * h * d * seq * seq / 2
    nbytes = batch * seq * d * itemsize * (2 * h + 2 * kv) \
        + batch * h * seq * 4                      # + logsumexp rows, fp32
    return flops, nbytes


def flash_bwd_cost(cfg, batch: int, seq: int, itemsize: int = 2):
    """(flops, bytes) of ONE causal flash backward (dq and dkv together):
    the five products the gradient needs (S, dP, dQ, dK, dV) over the lower
    triangle; the second S and dP that the two-kernel split recomputes are
    not required work. Q, K, V, O, dO read and dQ, dK, dV written once."""
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    flops = 5 * 2 * batch * h * d * seq * seq / 2
    nbytes = batch * seq * d * itemsize * (4 * h + 4 * kv) \
        + 2 * batch * h * seq * 4                  # + logsumexp and delta rows
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """K and V rows of one cached token over all layers."""
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * head_dim(cfg) * itemsize


def decode_step_bytes(cfg, context_tokens: int, itemsize: int = 2) -> int:
    """Bytes one decode step must read: every matmul weight once, plus the
    cached K and V of the running sequences (``context_tokens`` in all)."""
    return matmul_params(cfg) * itemsize \
        + context_tokens * kv_bytes_per_token(cfg, itemsize)
