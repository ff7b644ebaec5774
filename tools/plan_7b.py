#!/usr/bin/env python
"""Llama-2-7B flagship memory plan for a v5e-16 pod (VERDICT r3 #4).

AOT-compiles the FULL sharded train step (forward + backward + AdamW with
fp32 master weights, bf16 compute) for a 16-device mesh and reports XLA's
per-chip memory estimate from buffer assignment — no parameter buffer is
ever materialized (a 7B model cannot exist on a 16-virtual-device host:
replicated bf16 weights alone would need 216 GB).

The step is a PURE function: the parameter/optimizer pytree is an
argument (ShapeDtypeStruct at compile time), mirroring the shapes, dtypes
and math of paddle_tpu/models/llama.py (RMSNorm -> GQA-capable attention
-> SwiGLU, scan over stacked [L, ...] weights, jax.checkpoint remat) and
the sharding plan of shard_llama/shard_optimizer:

  - s2  (fleet sharding stage-2 analog, BASELINE.md config 3): parameters
    REPLICATED, optimizer states + master weights sharded over the 16
    chips. The reference runs this on 80 GB H100s; the plan quantifies
    why a 16 GB v5e cannot hold replicated 7B bf16 weights (13.5 GB)
    plus gradients and activations.
  - s3  (ZeRO-3 / FSDP analog, shard_llama fsdp_axis): parameters,
    masters and optimizer states all sharded; selective remat
    (dots_with_no_batch_dims_saveable, the bench.py policy).
  - s3_full: same with full per-layer remat (minimum activation memory).

Caveats (stated in the report): the CPU backend compiles XLA attention
(Mosaic/Pallas flash cannot target CPU), so the S^2 attention workspace in
`temp` is an overestimate versus the TPU path where flash streams it; and
buffer sizes come from XLA:CPU buffer assignment at identical
shapes/shardings, not a TPU HLO schedule.

Usage:  python tools/plan_7b.py            # self-execs on a 16-CPU mesh
        python tools/plan_7b.py --execute  # scaled-down real step (8 mesh)
Writes PLAN_7B.json at the repo root.

Reference parity targets: BASELINE.md config 3;
fleet/meta_parallel/sharding/group_sharded_stage2.py:46 (reference stage-2),
group_sharded_stage3.py:85 (stage-3 prefetch/offload analog).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "PLAN_7B.json")

GIB = 1024 ** 3
V5E_HBM_GIB = 16.0


def _llama7b_dims():
    """Mirror of paddle_tpu.models.llama.llama2_7b_config (32L/4096H/32
    heads, MHA, vocab 32000, SwiGLU 11008)."""
    return dict(L=32, H=4096, I=11008, V=32000, heads=32, kv_heads=32)


def _tiny_dims():
    return dict(L=4, H=256, I=688, V=2000, heads=8, kv_heads=8)


def _param_shapes(d):
    L, H, I, V = d["L"], d["H"], d["I"], d["V"]
    return {
        "embed": (V, H),
        "wq": (L, H, H), "wk": (L, H, H), "wv": (L, H, H), "wo": (L, H, H),
        "w_gate": (L, H, I), "w_up": (L, H, I), "w_down": (L, I, H),
        "ln1": (L, H), "ln2": (L, H), "ln_f": (H,),
        "lm_head": (H, V),
    }


def _build_step(d, batch, seq, remat, variant="s3", mesh=None):
    """Pure train step: (state, ids, labels) -> (state, loss).

    state = {params(bf16), master(f32), m(f32), v(f32), step(i32)}; math
    mirrors models/llama.py (cited there against the reference's fused
    kernels) and optimizer.AdamW with multi_precision=True.

    variant "s3" (ZeRO-3/FSDP): the bf16 compute params are DERIVED from
    the sharded fp32 master inside the step (state["params"] exists for
    checkpoint parity but the step never reads it, so XLA prunes it);
    per-layer weight gathers appear as temps.
    variant "s2" (stage-2): bf16 params are live REPLICATED state; grads
    are constrained to the sharded layout (GSPMD lowers the data-parallel
    reduction to a reduce-scatter, the reference's stage-2 grad sharding),
    the sharded fp32 master updates, and the new replicated params are
    all-gathered back — so the 13.5 GB replicated weight residency is
    honestly part of the per-chip estimate.
    """
    import jax
    import jax.numpy as jnp

    heads, kv_heads = d["heads"], d["kv_heads"]
    head_dim = d["H"] // heads
    scale = head_dim ** -0.5

    def rms(x, w, eps=1e-5):
        r = jax.lax.rsqrt(jnp.mean(
            x.astype(jnp.float32) ** 2, -1, keepdims=True) + eps)
        return (x * r.astype(x.dtype)) * w

    def rope(x, pos):
        # [B, S, h, dh] -> rotate pairs; mirrors llama.py _rope_cos_sin
        half = head_dim // 2
        inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * inv[None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        cos = cos[None, :, None, :].astype(x.dtype)
        sin = sin[None, :, None, :].astype(x.dtype)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    def _anchor(h):
        # activation anchor (mirrors shard_llama's batch_axes install):
        # batch stays sharded over the mesh, hidden replicated — without
        # it GSPMD may all-gather the batch to resolve the batch-sharded x
        # vs in-dim-sharded w conflict, 16x-ing every saved residual
        if mesh is None:
            return h
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P("z", *([None] * (h.ndim - 1)))))

    def layer(h, w):
        h = _anchor(h)
        B, S, H = h.shape
        pos = jnp.arange(S)
        x = rms(h, w["ln1"])
        q = (x @ w["wq"]).reshape(B, S, heads, head_dim)
        k = (x @ w["wk"]).reshape(B, S, kv_heads, head_dim)
        v = (x @ w["wv"]).reshape(B, S, kv_heads, head_dim)
        q, k = rope(q, pos), rope(k, pos)
        if kv_heads != heads:
            k = jnp.repeat(k, heads // kv_heads, 2)
            v = jnp.repeat(v, heads // kv_heads, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(h.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H)
        h = h + att @ w["wo"]
        x = rms(h, w["ln2"])
        mlp = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
        return _anchor(h + mlp)

    if remat == "selective":
        layer = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat == "full":
        layer = jax.checkpoint(layer)

    def forward(params, ids, labels):
        h = params["embed"][ids]
        stacked = {k: params[k] for k in
                   ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                    "ln1", "ln2")}

        def body(h, w):
            return layer(h, w), None

        h, _ = jax.lax.scan(body, h, stacked)
        h = rms(h, params["ln_f"])
        logits = (h @ params["lm_head"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, labels[..., None], -1)
        return nll.mean()

    def _adamw(state, grads_f32):
        t = state["step"] + 1
        b1, b2, lr, eps, wd = 0.9, 0.999, 1e-4, 1e-8, 0.01
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                         state["m"], grads_f32)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                         state["v"], grads_f32)
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)
        master = jax.tree.map(
            lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2)
                                                     + eps) + wd * p),
            state["master"], m, v)
        return master, m, v, t

    def step_s3(state, ids, labels):
        def loss_of_master(master):
            params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), master)
            return forward(params, ids, labels)

        loss, grads = jax.value_and_grad(loss_of_master)(state["master"])
        master, m, v, t = _adamw(state, grads)
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), master)
        return {"params": params, "master": master, "m": m, "v": v,
                "step": t}, loss

    def step_s2(state, ids, labels):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.lax import with_sharding_constraint as wsc

        sharded, replicated = _s2_grad_shardings(d, mesh)
        loss, grads = jax.value_and_grad(
            lambda p: forward(p, ids, labels))(state["params"])
        # stage-2: grads live SHARDED (GSPMD lowers the DP reduction to a
        # reduce-scatter instead of an all-reduce)
        grads = jax.tree.map(lambda g, s: wsc(g.astype(jnp.float32), s),
                             grads, sharded)
        master, m, v, t = _adamw(state, grads)
        # updated params all-gather back to the replicated layout
        params = jax.tree.map(
            lambda x, r: wsc(x.astype(jnp.bfloat16), r), master, replicated)
        return {"params": params, "master": master, "m": m, "v": v,
                "step": t}, loss

    return step_s2 if variant == "s2" else step_s3


def _s2_grad_shardings(d, mesh):
    """(sharded, replicated) NamedSharding trees over the param shapes."""
    from jax.sharding import NamedSharding
    sharded_tree, _ = _shardings(d, mesh, "s3")
    sharded = sharded_tree["master"]
    rep_tree, _ = _shardings(d, mesh, "s2")
    replicated = rep_tree["params"]
    return sharded, replicated


def _shardings(d, mesh, variant):
    """NamedShardings mirroring shard_llama(fsdp_axis='z') /
    shard_optimizer: s3 shards every >=2D weight on a non-layer dim; s2
    replicates params but shards master/m/v (stage-2: optimizer-state +
    grad sharding, parameters replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def shard_spec(name, shape):
        if name in ("ln1", "ln2", "ln_f"):
            return P()  # per-layer norm scales: tiny, replicate
        if len(shape) == 2:  # embed [V,H] / lm_head [H,V]: shard dim 0
            return P("z", None)
        return P(None, "z", None)  # stacked [L, in, out]: shard `in`

    def of(spec):
        return NamedSharding(mesh, spec)

    shapes = _param_shapes(d)
    sharded = {k: of(shard_spec(k, s)) for k, s in shapes.items()}
    replicated = {k: of(P()) for k in shapes}
    opt_tree = sharded  # master/m/v always sharded (both variants)
    params_tree = replicated if variant == "s2" else sharded
    state_shardings = {"params": params_tree, "master": opt_tree,
                       "m": dict(opt_tree), "v": dict(opt_tree),
                       "step": of(P())}
    data_sharding = of(P("z", None))  # batch over the mesh
    return state_shardings, data_sharding


def _abstract_state(d):
    import jax
    import jax.numpy as jnp
    shapes = _param_shapes(d)

    def tree(dtype):
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}

    return {"params": tree(jnp.bfloat16), "master": tree(jnp.float32),
            "m": tree(jnp.float32), "v": tree(jnp.float32),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _compile_variant(d, mesh, variant, remat, batch, seq):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    step = _build_step(d, batch, seq, remat, variant=variant, mesh=mesh)
    state_sh, data_sh = _shardings(d, mesh, variant)
    state = _abstract_state(d)

    def with_sh(tree, sh):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)

    state = {k: (with_sh(state[k], state_sh[k])
                 if isinstance(state[k], dict)
                 else jax.ShapeDtypeStruct(state[k].shape, state[k].dtype,
                                           sharding=state_sh[k]))
             for k in state}
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data_sh)
    labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data_sh)

    jitted = jax.jit(step, donate_argnums=(0,))
    compiled = jitted.lower(state, ids, labels).compile()
    ma = compiled.memory_analysis()
    n_params = sum(
        functools.reduce(lambda a, b: a * b, s, 1)
        for s in _param_shapes(d).values())
    rec = {
        "variant": variant, "remat": remat, "batch": batch, "seq": seq,
        "n_params": n_params,
        "per_chip_bytes": {
            "arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "aliased": ma.alias_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
        },
    }
    # resident = donated-in state (arguments) + workspace; donated outputs
    # alias the inputs so they are not double-counted
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes))
    rec["per_chip_live_gib"] = round(live / GIB, 3)
    rec["fits_v5e_16gib"] = bool(live / GIB <= V5E_HBM_GIB)
    return rec


VARIANTS = {"s2": ("s2", "selective"), "s3": ("s3", "selective"),
            "s3_full": ("s3", "full")}


def run_plan(n_devices=16, batch=16, seq=2048, execute=False,
             variants=None):
    import numpy as np
    import jax

    devs = jax.devices()
    assert len(devs) >= n_devices, (len(devs), n_devices)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devs[:n_devices]), ("z",))

    d = _llama7b_dims()
    report = {"topology": f"{n_devices}-chip mesh (v5e-16 analog)",
              "hbm_per_chip_gib": V5E_HBM_GIB,
              "model": "llama2-7b (32L/4096H/32 heads, MHA, vocab 32000)",
              "backend": jax.devices()[0].platform,
              "note": ("compile-only buffer-assignment estimate on the CPU "
                       "backend at identical shapes/shardings; XLA "
                       "attention (no Mosaic flash on CPU) makes `temp` an "
                       "overestimate of the TPU flash path"),
              "variants": []}
    # a partial (--variants) run must not erase other variants' evidence
    try:
        with open(OUT) as f:
            prev = json.load(f)
        report["variants"] = prev.get("variants", [])
        # evidence blocks owned by sibling tools must survive a re-plan
        # (tools/slice_7b.py writes slice_7b; erasing it would let this
        # tool's own test delete the measured per-layer record)
        for carry in ("scaled_execute", "slice_7b"):
            if carry in prev:
                report[carry] = prev[carry]
    except (OSError, json.JSONDecodeError):
        pass
    wanted = variants or list(VARIANTS)
    with mesh:
        for name in wanted:
            variant, remat = VARIANTS[name]
            print(f"[plan7b] compiling {name} ...", flush=True)
            rec = _compile_variant(d, mesh, variant, remat, batch, seq)
            rec["name"] = name
            report["variants"] = [v for v in report["variants"]
                                  if v["name"] != name] + [rec]
            print(f"[plan7b] {name}: live/chip = "
                  f"{rec['per_chip_live_gib']} GiB "
                  f"(fits 16G: {rec['fits_v5e_16gib']})", flush=True)
            _write(report)  # persist incrementally: a later failure must
            # not lose the compile evidence

    if execute:
        # scaled-down, SAME structure/shardings/remat: prove the compiled
        # step actually runs and produces a finite loss on an 8-chip mesh
        td = _tiny_dims()
        n = min(8, len(devs))
        tmesh = Mesh(np.array(devs[:n]), ("z",))
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        with tmesh:
            step = _build_step(td, n, 128, "selective", mesh=tmesh)
            state_sh, data_sh = _shardings(td, tmesh, "s3")
            shapes = _param_shapes(td)

            def init(dtype):
                return {k: jnp.asarray(rng.randn(*s) * 0.02, dtype)
                        for k, s in shapes.items()}

            master = init(jnp.float32)
            state = {"params": jax.tree.map(
                         lambda x: x.astype(jnp.bfloat16), master),
                     "master": master,
                     "m": jax.tree.map(jnp.zeros_like, master),
                     "v": jax.tree.map(jnp.zeros_like, master),
                     "step": jnp.asarray(0, jnp.int32)}
            state = {
                k: (jax.tree.map(jax.device_put, state[k], state_sh[k])
                    if isinstance(state[k], dict)
                    else jax.device_put(state[k], state_sh[k]))
                for k in state}
            ids = jax.device_put(
                jnp.asarray(rng.randint(0, td["V"], (n, 128))), data_sh)
            labels = jax.device_put(
                jnp.asarray(rng.randint(0, td["V"], (n, 128))), data_sh)
            jstep = jax.jit(step, donate_argnums=(0,))
            state, loss0 = jstep(state, ids, labels)
            state, loss1 = jstep(state, ids, labels)
            report["scaled_execute"] = {
                "dims": td, "mesh": n, "loss0": float(loss0),
                "loss1": float(loss1),
                "ok": bool(np.isfinite(float(loss0))
                           and np.isfinite(float(loss1))
                           and float(loss1) < float(loss0)),
            }
            print(f"[plan7b] scaled execute: loss {float(loss0):.4f} -> "
                  f"{float(loss1):.4f}", flush=True)

    _write(report)
    return report


def _write(report):
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    os.replace(tmp, OUT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--execute", action="store_true")
    ap.add_argument("--inproc", action="store_true")
    ap.add_argument("--devices", type=int, default=16)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--variants", help="comma-separated subset of "
                    f"{sorted(VARIANTS)} (default: all)")
    args = ap.parse_args()
    if args.variants:
        unknown = [v for v in args.variants.split(",")
                   if v not in VARIANTS]
        if unknown:
            ap.error(f"unknown variant(s) {unknown}")

    if not args.inproc:
        # self-exec on a virtual-CPU mesh (the parent stays off JAX; same
        # recipe as __graft_entry__.dryrun_multichip)
        import subprocess
        sys.path.insert(0, REPO)
        import __graft_entry__ as graft
        env = dict(os.environ)
        graft.force_cpu_env(env, args.devices)
        cmd = [sys.executable, os.path.abspath(__file__), "--inproc",
               "--devices", str(args.devices), "--batch", str(args.batch),
               "--seq", str(args.seq)]
        if args.variants:
            cmd += ["--variants", args.variants]
        if args.execute:
            cmd.append("--execute")
        return subprocess.run(cmd, env=env, cwd=REPO, timeout=1800).returncode

    report = run_plan(args.devices, args.batch, args.seq, args.execute,
                      args.variants.split(",") if args.variants else None)
    fitting = [v["name"] for v in report["variants"] if v["fits_v5e_16gib"]]
    print(json.dumps({"fitting_variants": fitting}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
