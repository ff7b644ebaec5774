#!/usr/bin/env python3
"""By hand, on a CPU: python3 chipbench/tests/keye_draws.py ['{"o_w": 0.5}']

How ``chipbench/families/keye.py::leaf_draw`` was chosen, from the plain
reference alone: a twin of ``keye-vl-2.0-30b-a3b-serve-d6`` at a middle size
(hidden 512, 4 layers, 8 / 2 heads of 128, 32 experts of 4, 256 rows kept of
TOKENS) whose every product keeps the published one's gain (``range x
sqrt(fan-in)``: 0.04 at 512 is 0.02 at 2,048). Printed for two seeds, as
(99th percentile, mean, share off the reference's choice) of the served-logit
gap over the last 1,536 rows: the equations' own bfloat16 and float8 operand
roundings (``reference.einsum``'s: what any sound bfloat16 program, and the
control, cannot read under), how far apart they lie, and the three omissions
of ``keye_omission.py``. The argument scales leaves' draws (a gain's centre,
the embedding's or a matrix's scale times the number); none is the family's
``leaf_draw`` as it stands.

What it read (PERF.md section 6, PR 42; the twin has 4 layers, so ``'{"o_w":
2.83, "exp_w2": 2.83}'`` is every matrix at ``initializer_range``): every
matrix at ``initializer_range`` 2.3 to 2.9 / 4.1 to 5.0 / 2.0 to 2.3 times
apart at 2,048 and at 4,096 tokens, as the chip then read the program (2.30 /
4.6 / 2.05); q and k gains of 1.5 beside that (``"q_g": 1.5, "k_g": 1.5``)
bfloat16 alone 0.85 / 0.096 / 39%, where the chip's sound program had read
0.63 / 0.096 / 45%: the equations' sensitivity, not a fault of the kept-row
path; the family's draws (``W_o`` and ``W2`` at ``1 / sqrt(2 x layers)``) 23
to 25 / 16 to 38 / 5 to 7 times apart at 2,048 tokens (7 / 14 / 3.7 at 4,096)
with every omission past float8 by 1.5 times and more.
"""
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness as H, serve  # noqa: E402
from chipbench import reference as R, weights as W  # noqa: E402
from chipbench.families import keye as K  # noqa: E402

TOKENS, ROWS = int(os.environ.get("DRAWS_TOKENS", 2048)), 1536
KEYS = ("served_logit_gap_p99", "served_logit_gap_mean",
        "served_off_argmax_share")


def twin() -> dict:
    base = H.load_config("keye-vl-2.0-30b-a3b-serve-d6", False)
    cfg = dict(base, hidden_size=512, num_hidden_layers=4,
               num_attention_heads=8, num_key_value_heads=2, num_experts=32,
               num_local_experts=32, num_experts_per_tok=4,
               moe_intermediate_size=192, vocab_size=32768,
               initializer_range=0.04, max_position_embeddings=2 * TOKENS,
               sa_config=dict(base["sa_config"], topk=256,
                              indexer_num_heads=8))
    cfg.pop("runner")
    return cfg


def logits(cfg, seed, ids, precision="f32", **kw):
    tables = K.position_tables(len(ids), cfg)
    top = R._f32(W.make_top(cfg, seed))
    x = K.embed_tokens(jnp.asarray(ids), top, cfg)

    @functools.partial(jax.jit, static_argnums=(3,))
    def layer(x, w, tables, i):
        return K.layer_forward(x, R._f32(w), tables, cfg, i, precision, **kw)

    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, W.make_layer(cfg, seed, i), tables, 0)
    return np.asarray(jax.jit(lambda x, top: K.head_logits(
        x, top, cfg, precision))(x[-ROWS:], top))


def main():
    scaled = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    family_draw = K.leaf_draw

    def draw(cfg, leaf):
        how, value = family_draw(cfg, leaf)
        if leaf not in scaled:
            return how, value
        return how, scaled[leaf] * (1.0 if how == "gain" else value)

    K.leaf_draw = draw
    cfg = twin()
    for seed in (11, 12):
        ids = np.random.default_rng(seed).integers(0, cfg["vocab_size"],
                                                   TOKENS)
        whole = logits(cfg, seed, ids)
        read = {}
        for name, kw in (("bfloat16", {"precision": "bf16"}),
                         ("float8", {"precision": "fp8"}),
                         ("newest_rows", {"selection": "newest"}),
                         ("every_row", {"selection": "all"}),
                         ("two_experts_of_four", {"experts_kept": 2})):
            got = serve.gap_statistics(serve.token_gaps(
                [whole], [logits(cfg, seed, ids, **kw).argmax(-1)]))
            read[name] = [round(got[k], 5) for k in KEYS]
        read["float8_over_bfloat16"] = [
            round(b / max(a, 1e-9), 2)
            for a, b in zip(read["bfloat16"], read["float8"])]
        print(f"draws: {json.dumps(dict(seed=seed, scaled=scaled, **read))}",
              flush=True)


if __name__ == "__main__":
    main()
