#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process drives the two main paths once, through the entry points a user
calls, at the published widths of ``llama2_7b_config()`` (hidden 4096,
32 heads x 128, MLP 11008, vocab 32000, sequence 2048) with depth cut to what
one 16 GB v5e holds and random weights made from ``--seed``:

  trainer  ``jit.TrainStep`` over ``LlamaForCausalLM`` (scanned stack,
           selective recompute) under ``AdamW(multi_precision=True)`` +
           ``amp.decorate(O2, bfloat16)``: discovery pass, compile, warm
           steps on one repeated batch. Checks: loss finite and falling, no
           compile during the warm steps, and the compiled step's HLO calls
           the Pallas flash-attention and rmsnorm kernels (not their XLA
           references).
  server   the same widths in bf16 behind ``inference.gateway.Gateway`` with
           one ``PagedContinuousBatcher(compile=True, prefix_cache=True,
           prefill_chunk=256)`` replica: requests of mixed prompt length,
           two sharing a prefix.
           Checks: every request returns ``max_new_tokens`` tokens, each
           served token's logit in the model's plain un-paged forward lies
           within a bf16 tolerance of that forward's best (the batcher is
           greedy: its executables choose, no logits reach the host), the
           prefix cache was hit, and ``audit_pages() == 0``.

``--chips 4`` runs, instead of those two, only the sharded train step
(``MeshRuntime({"fsdp": 2, "tensor": 2})`` + ``TrainMeshPlan``) and the same
step on one device that it is compared with.

``--rehearse`` changes sizes only (tiny widths, same code path); the device
assertion stays. There is no CPU branch: without a TPU the script exits
non-zero and prints no result. It claims no speed: the seconds it prints are
set-up costs (compile, cache), not measurements.

Last line of stdout, exactly:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import time


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden: int
    heads: int
    mlp: int
    vocab: int
    seq: int
    # trainer: depth that fits 16 GB with bf16 weight + fp32 master + Adam
    # m/v (14 B per parameter) beside the activations of the batch
    train_layers: int
    train_batch: int
    warm_steps: int
    # server: depth that leaves room for a real page pool
    serve_layers: int
    prompt_lens: tuple      # the last two share their first `shared` tokens
    shared: int
    max_new: int
    block: int
    n_pages: int
    prefill_chunk: int


REAL = Sizes(hidden=4096, heads=32, mlp=11008, vocab=32000, seq=2048,
             train_layers=2, train_batch=2, warm_steps=3,
             serve_layers=8, prompt_lens=(64, 300, 640, 1024), shared=512,
             max_new=8, block=16, n_pages=1536, prefill_chunk=256)
REHEARSE = Sizes(hidden=256, heads=2, mlp=512, vocab=512, seq=256,
                 train_layers=2, train_batch=1, warm_steps=3,
                 serve_layers=2, prompt_lens=(8, 20, 40, 64), shared=32,
                 max_new=4, block=8, n_pages=64, prefill_chunk=16)

TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "rms_norm_fwd", "rms_norm_bwd")
# bf16 keeps 8 bits of mantissa; the paged path (fp32 softmax over gathered
# pages) and the plain forward (flash kernel) round differently at every
# layer. A served token's distance below the plain forward's best, not
# equal tokens: random weights put argmax on near-ties.
LOGIT_TOL = 2.0 ** -4       # of the largest |logit| of the plain forward
# the sharded step gathers parameters at use and computes what one device
# computes; bf16 fusion boundaries may still differ between the programs
MESH_LOSS_RTOL = 1e-2


def say(key, value):
    print(f"{key}: {value}", flush=True)


def require_tpu(min_devices=1):
    """The device assertion: JAX's own answer, no fallback."""
    import jax
    devices = jax.devices()     # raises where no backend can start
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX reports platform "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < min_devices:
        raise SystemExit(f"chip_smoke: needs {min_devices} chips, JAX "
                         f"reports {len(devices)}")
    return devices


def require_kernels(hlo_text, names):
    """Each named Pallas kernel is a tpu_custom_call of the optimized HLO:
    the kernels ran, not their XLA references."""
    calls = [ln for ln in hlo_text.splitlines() if "tpu_custom_call" in ln]
    missing = [n for n in names if not any(n in ln for ln in calls)]
    if missing:
        raise AssertionError(f"no tpu_custom_call for {missing} among the "
                             f"{len(calls)} custom calls of the step")
    return len(calls)


def peak_bytes(device):
    stats = device.memory_stats()       # None where the backend has none
    return None if stats is None else stats.get("peak_bytes_in_use")


def llama_config(sz, layers, **kw):
    from paddle_tpu.models.llama import llama2_7b_config
    return llama2_7b_config(
        hidden_size=sz.hidden, num_attention_heads=sz.heads,
        num_key_value_heads=sz.heads, intermediate_size=sz.mlp,
        vocab_size=sz.vocab, max_position_embeddings=sz.seq,
        num_hidden_layers=layers, **kw)


def build_train_step(sz, layers, seed, mesh_plan=None):
    """Model, optimizer and TrainStep as bench.py and a user build them."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit, optimizer
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(llama_config(
        sz, layers, scan_layers=True, use_recompute=True,
        recompute_granularity="selective"))
    opt = optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                          parameters=model.parameters(), multi_precision=True)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def loss_fn(ids, labels):
        return model(ids, labels=labels)[1]

    return model, jit.TrainStep(loss_fn, opt, mesh_plan=mesh_plan)


def train_batches(sz, seed, batch):
    """(discovery batch, real batch): the eager discovery pass keeps every
    vjp residual, so it runs on one short sequence."""
    import numpy as np
    import paddle_tpu as paddle
    rng = np.random.RandomState(seed)

    def pair(b, s):
        return tuple(paddle.to_tensor(rng.randint(0, sz.vocab, (b, s)))
                     for _ in range(2))

    return pair(batch, min(128, sz.seq)), pair(batch, sz.seq)


def run_steps(step, batch, n):
    return [float(step(*batch)) for _ in range(n)]   # float() waits


def trainer_phase(sz, seed, device):
    import math
    from paddle_tpu.perf.compile_cache import compile_metrics

    t0 = time.perf_counter()
    model, step = build_train_step(sz, sz.train_layers, seed)
    say("trainer.config", f"hidden {sz.hidden}, {sz.heads} heads x "
        f"{sz.hidden // sz.heads}, mlp {sz.mlp}, vocab {sz.vocab}, seq "
        f"{sz.seq}, depth {sz.train_layers}, batch {sz.train_batch}")
    say("trainer.params", model.num_params())
    small, real = train_batches(sz, seed, sz.train_batch)
    step(*small)                                     # eager discovery
    say("trainer.peak_bytes_after_discovery", peak_bytes(device))
    t1 = time.perf_counter()
    first = run_steps(step, real, 1)                 # compiles
    t2 = time.perf_counter()
    before = compile_metrics()
    warm = run_steps(step, real, sz.warm_steps)
    after = compile_metrics()
    losses = first + warm
    say("trainer.setup_s", f"build+discovery {t1 - t0:.1f}, compile+first "
        f"step {t2 - t1:.1f}")
    say("trainer.losses", [round(x, 4) for x in losses])
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if after["compile_cache_misses"] != before["compile_cache_misses"]:
        raise AssertionError(f"compiled during the warm steps: "
                             f"{before} -> {after}")
    n_calls = require_kernels(step.aot_compile(*real).as_text(),
                              TRAIN_KERNELS)
    say("trainer.kernels", f"{', '.join(TRAIN_KERNELS)} present among "
        f"{n_calls} tpu_custom_call")
    say("trainer.compile", compile_metrics())
    say("trainer.peak_bytes", peak_bytes(device))


def server_phase(sz, seed, device):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference.gateway import Gateway
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.perf.compile_cache import compile_metrics

    t0 = time.perf_counter()
    paddle.seed(seed)
    model = LlamaForCausalLM(llama_config(sz, sz.serve_layers,
                                          dtype="bfloat16"))
    model.eval()
    say("server.config", f"hidden {sz.hidden}, {sz.heads} heads x "
        f"{sz.hidden // sz.heads}, mlp {sz.mlp}, vocab {sz.vocab}, depth "
        f"{sz.serve_layers}, bf16, {sz.n_pages} pages x {sz.block} tokens, "
        f"prefill chunk {sz.prefill_chunk}")
    say("server.params", model.num_params())

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, sz.vocab, n) for n in sz.prompt_lens]
    prompts[-1][:sz.shared] = prompts[-2][:sz.shared]

    # prefill_chunk: prompts enter through one compiled, pool-donating
    # executable. Without it admission runs eagerly, op by op, holding two
    # copies of the page pool: at these sizes the chip ran out of memory.
    batcher = PagedContinuousBatcher(
        model, max_batch=len(prompts), s_max=sz.seq, block_size=sz.block,
        n_pages=sz.n_pages, compile=True, prefix_cache=True,
        prefill_chunk=sz.prefill_chunk)
    try:
        gateway = Gateway()
        gateway.add_replica("chip0", batcher)
        gids = [gateway.submit(p, sz.max_new) for p in prompts]
        done = gateway.run_until_done()
        t1 = time.perf_counter()
        for gid, prompt in zip(gids, prompts):
            out = done[gid]
            if len(out) != len(prompt) + sz.max_new \
                    or not np.array_equal(out[:len(prompt)], prompt):
                raise AssertionError(
                    f"request {gid}: {len(out) - len(prompt)} of "
                    f"{sz.max_new} tokens for a {len(prompt)}-token prompt")
        say("server.tokens", {f"prompt{len(p)}": sz.max_new
                              for p in prompts})
        hit = batcher.prefix_cache.hit_tokens
        if hit < sz.shared // sz.block * sz.block:
            raise AssertionError(f"prefix cache served {hit} tokens of a "
                                 f"{sz.shared}-token shared prefix")
        say("server.prefix_hit_tokens", hit)

        # the plain forward over what was served, all sequences right-padded
        # into one batch: under the causal mask a position's logits do not
        # see the padding after it, and one shape compiles once
        seqs = [done[gid] for gid in gids]
        ids = np.zeros((len(seqs), max(len(s) for s in seqs)), np.int64)
        for row, seq in zip(ids, seqs):
            row[:len(seq)] = seq
        with paddle.no_grad():
            plain_all = model(paddle.to_tensor(ids))._data
        gaps = {}
        for i, (prompt, seq) in enumerate(zip(prompts, seqs)):
            at = np.arange(len(prompt) - 1, len(seq) - 1)
            plain = np.asarray(plain_all[i, at], np.float32)
            if not np.isfinite(plain).all():
                raise AssertionError("plain forward: non-finite logits")
            served = plain[np.arange(len(at)), seq[at + 1]]
            gaps[f"prompt{len(prompt)}"] = round(float(
                (plain.max(-1) - served).max() / np.abs(plain).max()), 5)
        worst = max(gaps.values())
        say("server.token_gap", f"{gaps} of max |logit| (tolerance "
            f"{LOGIT_TOL:.5f})")
        if worst > LOGIT_TOL:
            raise AssertionError("a served token lies below the plain "
                                 "forward's best")
        leaked = batcher.audit_pages()
        if leaked:
            raise AssertionError(f"audit_pages() == {leaked}")
        say("server.audit_pages", leaked)
    finally:
        batcher.close()
    say("server.setup_s", f"build+serve {t1 - t0:.1f} (compiles included)")
    say("server.compile", compile_metrics())
    say("server.peak_bytes", peak_bytes(device))


def sharded_phase(sz, seed, devices):
    """--chips 4: the trainer's step as one SPMD program, against the same
    step on one device (which one chip must therefore also hold)."""
    import math
    from paddle_tpu.distributed.mesh import MeshRuntime

    small, real = train_batches(sz, seed, sz.train_batch)
    runtime = MeshRuntime({"fsdp": 2, "tensor": 2}, devices=devices[:4])
    plan = runtime.train_plan()
    model, step = build_train_step(sz, sz.train_layers, seed, mesh_plan=plan)
    say("sharded.config", f"mesh fsdp 2 x tensor 2, hidden {sz.hidden}, "
        f"depth {sz.train_layers}, seq {sz.seq}, {model.num_params()} params")
    step(*small)
    sharded = run_steps(step, real, sz.warm_steps)
    say("sharded.losses", [round(x, 4) for x in sharded])

    # placement: every sharded parameter lives on four distinct chips, and
    # no chip holds more than its share of the whole
    total = 0
    per_device = {d.id: 0 for d in devices[:4]}
    for p in model.parameters():
        arr = p._data
        if len(arr.sharding.device_set) != 4:
            raise AssertionError(f"{p.name}: on {len(arr.sharding.device_set)}"
                                 f" devices, not 4")
        total += arr.nbytes
        for shard in arr.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    say("sharded.param_bytes", {"total": total, "per_device": per_device})
    if max(per_device.values()) > 0.5 * total:
        raise AssertionError("parameters are not spread over the mesh")
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:4]}
    say("sharded.bytes_in_use", in_use)
    if all(v is not None for v in in_use.values()):
        if min(in_use.values()) < 0.5 * max(in_use.values()):
            raise AssertionError("device memory is not spread over the mesh")

    hlo = step.aot_compile(*real).as_text()
    collectives = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                   for k in ("all-gather", "reduce-scatter", "all-reduce",
                             "collective-permute", "all-to-all")}
    say("sharded.collectives", collectives)
    # gather-at-use storage sharding with a replicated batch: parameters
    # are all-gathered for compute, gradients are sliced, not reduced
    if not collectives["all-gather"]:
        raise AssertionError("the sharded step gathers no parameter")
    require_kernels(hlo, TRAIN_KERNELS)

    del model, step, plan
    gc.collect()                # the sharded state leaves the chips
    _, single_step = build_train_step(sz, sz.train_layers, seed)
    single_step(*small)
    single = run_steps(single_step, real, sz.warm_steps)
    say("sharded.single_device_losses", [round(x, 4) for x in single])
    for a, b in zip(sharded, single):
        if not (math.isfinite(a) and math.isclose(a, b,
                                                  rel_tol=MESH_LOSS_RTOL)):
            raise AssertionError(f"sharded {sharded} vs single {single}")
    say("sharded.max_rel_diff", max(abs(a - b) / abs(b)
                                    for a, b in zip(sharded, single)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step and the "
                         "single-device step it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, same path (sizes only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sz = REHEARSE if args.rehearse else REAL

    devices = require_tpu(min_devices=args.chips)

    import jax
    import jaxlib
    from paddle_tpu.core.native import native_available, native_error
    from paddle_tpu.perf.compile_cache import (compile_metrics,
                                               enable_persistent_cache)
    say("versions", {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": _libtpu_version()})
    say("device", f"{devices[0].device_kind} x {len(devices)}")
    say("compile_cache_dir", enable_persistent_cache())
    say("native", {"available": native_available(),
                   "error": native_error()})

    if args.chips == 4:
        sharded_phase(sz, args.seed, devices)
    else:
        trainer_phase(sz, args.seed, devices[0])
        gc.collect()            # the trainer's state leaves the chip
        server_phase(sz, args.seed, devices[0])
    say("compile_cache", compile_metrics())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


def _libtpu_version():
    from importlib import metadata
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    main()
