#!/usr/bin/env python3
"""By hand, on the chip: python3 chipbench/tests/lfm2_omission.py [seed [tokens]]

What leaving out a part of LFM2's mathematics does to the logits, from the
plain reference alone, at the published widths of ``lfm2-24b-a2b-serve-d9``
on one sequence of 21,504 tokens: a hit resumed from zeros and not from its
snapshot (every convolution layer reads ``z`` before the boundary as zero),
once with the boundary right before the judged rows and once 640 rows before
them (where a served answer lies: behind the new prompt), the selection bias
left out of the choice, the bias let into the gates, and the 2 best experts
in the 4's place. Beside them the two roundings of the whole model: bfloat16
(the stand-in for a sound program) and float8 (the control). Printed for
each: the largest and the mean move of a logit over the last 512 rows, and
the served-logit-gap statistics of the departed model's own greedy tokens
judged on the full reference (what the cell's limits would read if the
program made that departure). PERF.md section 2 keeps the numbers beside the
cell's limits. (The CPU tests hold each at the tiny size.)
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

from chipbench import families, harness as H, serve  # noqa: E402
from chipbench import reference as R, weights as W  # noqa: E402

TOKENS, ROWS = 21504, 512


def logits(cfg, seed, ids, precision="f32", **kw):
    family = families.of(cfg)
    tables = family.position_tables(len(ids), cfg)
    top = R._f32(W.make_top(cfg, seed))
    x = family.embed_tokens(jnp.asarray(ids), top, cfg)

    @functools.partial(jax.jit, static_argnums=(3,))
    def layer(x, w, tables, i):
        return family.layer_forward(x, R._f32(w), tables, cfg, i, precision,
                                    **kw)

    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, W.make_layer(cfg, seed, i), tables, i)
    return np.asarray(jax.jit(lambda x, top: family.head_logits(
        x, top, cfg, precision))(x[-ROWS:], top))


def main():
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3000004490
    tokens = int(sys.argv[2]) if len(sys.argv) > 2 else TOKENS
    cfg = H.load_config("lfm2-24b-a2b-serve-d9", False)
    every = cfg["snapshot_rows"]
    ids = np.random.default_rng(seed).integers(0, cfg["vocab_size"], tokens)
    whole = logits(cfg, seed, ids)
    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "tokens": tokens, "rows": ROWS}
    behind = (tokens - ROWS) // every * every
    for name, kw in (
            ("resumed_from_zeros", {"reset_at": behind}),
            ("resumed_from_zeros_640_rows_before",
             {"reset_at": behind - 5 * every}),
            ("bias_left_out_of_the_choice", {"bias": "none"}),
            ("bias_in_the_gates", {"bias": "gates"}),
            ("two_experts_of_four", {"experts_kept": 2}),
            ("bfloat16", {"precision": "bf16"}),
            ("float8", {"precision": "fp8"})):
        cut = logits(cfg, seed, ids, **kw)
        move = np.abs(cut - whole)
        out[name] = dict(
            serve.gap_statistics(serve.token_gaps([whole],
                                                  [cut.argmax(-1)])),
            logit_move_max=float(move.max()),
            logit_move_mean=float(move.mean()))
        print(f"omission.{name}: " + json.dumps(out[name]), flush=True)
    print("omission: " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
