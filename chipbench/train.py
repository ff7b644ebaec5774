"""Runner kind ``train``: the timed path is ``jit.TrainStep.__call__``.

Set-up builds ONE step object, drives it from the seed's weights through its
first ``check_steps`` steps through the window's own call and feed, and hands
that same object to the window. The plain reference follows those steps once
the window has closed and the program's state is freed.
"""
from __future__ import annotations

import gc

import numpy as np

from . import families
from . import harness as H
from . import traffic as T
from . import weights as W


def set_flags(cfg: dict):
    import paddle_tpu as paddle
    paddle.set_flags(cfg["runner"].get("flags", {}))


def build(cfg: dict, seed: int):
    """Model, optimizer and TrainStep as a user builds them, then the seed's
    weights installed."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit, optimizer

    run = cfg["runner"]
    # one program for every --seed: the generator's key ends up as a
    # constant of the compiled step (PERF.md, finding of PR 24), and the
    # weights, ids and order come from --seed through chipbench itself
    paddle.seed(0)
    model = families.of(cfg).program_model(cfg, **run["model"])
    o = run["optimizer"]
    params = list(model.parameters())
    opt = optimizer.AdamW(learning_rate=o["learning_rate"], beta1=o["beta1"],
                          beta2=o["beta2"], epsilon=o["epsilon"],
                          weight_decay=o["weight_decay"], parameters=params,
                          multi_precision=o["multi_precision"])
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    W.install(model, cfg, seed, scanned=run["model"]["scan_layers"])

    def loss_fn(ids, labels):
        return model(ids, labels=labels)[1]

    step = jit.TrainStep(loss_fn, opt, opprof_label="chipbench.train_step")
    return model, opt, step


def leaves_by_parameter(cfg: dict) -> dict:
    """{the program's parameter name: our leaf} of the scanned model."""
    family = families.of(cfg)
    leaves = list(family.top_shapes(cfg)) + list(family.layer_shapes(cfg, 0))
    return {family.parameter_name(leaf, None, True): leaf for leaf in leaves}


def restore(model, opt, cfg: dict, seed: int):
    """Load the checkpoint "seed weights, no optimizer history" through the
    public ``set_value`` / ``Optimizer.set_state_dict``: what a resumed job
    does. The eager discovery pass is a real update on a small batch; this
    puts the state back to step 0, so the compiled steps that follow are the
    first steps from the seed, as the reference takes them."""
    import jax.numpy as jnp
    W.install(model, cfg, seed, scanned=cfg["runner"]["model"]["scan_layers"])
    params = _by_state_prefix(model)
    todo = [(key, value._data.shape, value._data.dtype)
            for key, value in opt.state_dict().items()
            if key not in ("@step", "LR_Scheduler")]
    for key, shape, dtype in todo:      # one leaf at a time: the old one goes
        pname, acc = key.rsplit(".", 1)
        if acc == "master_weight":
            new = params[pname][1]._data.astype(jnp.float32)
        elif acc.endswith("_pow"):
            new = jnp.ones(shape, dtype)
        else:
            new = jnp.zeros(shape, dtype)
        opt.set_state_dict({key: new, "@step": 0})
        del new


def _by_state_prefix(model) -> dict:
    """{prefix of the optimizer's state keys: (parameter name, parameter)},
    by the rule ``Optimizer.state_dict`` names them."""
    return {(p.name or f"param_{i}"): (n, p)
            for i, (n, p) in enumerate(model.named_parameters())}


def state_norms(model, opt, cfg: dict, acc: str, minus_seed=None) -> dict:
    """Leaf norms of one optimizer accumulator (or of the master weights
    less the seed's weights), read from ``Optimizer.state_dict()``."""
    import jax
    import jax.numpy as jnp
    params = _by_state_prefix(model)
    leaf_of = leaves_by_parameter(cfg)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    out = {}
    for key, value in opt.state_dict().items():
        if not key.endswith("." + acc):
            continue
        leaf = leaf_of[params[key.rsplit(".", 1)[0]][0]]
        base = minus_seed[leaf] if minus_seed else jnp.zeros((), jnp.float32)
        out[leaf] = float(norm(value._data, base))
    return out


def numbers_for(prog: dict, ref: dict, limits: dict):
    """What decides ``correct`` for a training cell: each step's loss, the
    norm of the first gradient and the norm of the parameters' change, each
    beside its limit."""
    from . import reference as R
    numbers = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        numbers[f"loss{i}_gap"] = {"value": abs(a - b) / abs(b),
                                   "limit": limits["loss_gap"]}
    g, g_leaf = R.norm_gap(prog["grad_norms"], ref["grad_norms"])
    d, d_leaf = R.norm_gap(prog["delta_norms"], ref["delta_norms"])
    numbers["grad_norm_gap"] = {"value": g, "limit": limits["grad_norm_gap"]}
    numbers["delta_norm_gap"] = {"value": d,
                                 "limit": limits["delta_norm_gap"]}
    return numbers, {"grad": g_leaf, "delta": d_leaf}


def to_device(batch):
    import paddle_tpu as paddle
    return tuple(paddle.to_tensor(a) for a in batch)


def run(ctx) -> None:
    import jax
    from paddle_tpu.observability import opprof

    args, cfg, traffic = ctx["args"], ctx["cfg"], ctx["traffic"]
    seed, vocab = args.seed, cfg["vocab_size"]
    set_flags(cfg)
    opprof.enable()
    opprof.reset_captures()
    counter = H.CompileCounter()
    phases = {"start": H.clock() - ctx["t0"]}

    t = H.clock()
    model, opt, step = build(cfg, seed)
    phases["build"] = H.clock() - t
    t = H.clock()
    d = traffic["discovery"]
    step(*to_device(T.train_batch(traffic, vocab, seed, 0,
                                  (d["batch"], d["seq"]))))
    phases["discovery"] = H.clock() - t
    t = H.clock()
    restore(model, opt, cfg, seed)
    phases["restore"] = H.clock() - t

    # the first steps, through the window's own call and feed
    t = H.clock()
    n_check = traffic["check_steps"]
    prog = {"losses": []}
    b1 = cfg["runner"]["optimizer"]["beta1"]
    for k in range(1, n_check + 1):
        loss = step(*to_device(T.train_batch(traffic, vocab, seed, k)))
        prog["losses"].append(float(loss))
        if k == 1:
            prog["grad_norms"] = {
                leaf: n / (1.0 - b1) for leaf, n in
                state_norms(model, opt, cfg, "moment1").items()}
    seed_w = dict(W.make_stack(cfg, seed))
    seed_w.update(W.make_top(cfg, seed))
    prog["delta_norms"] = state_norms(model, opt, cfg, "master_weight", seed_w)
    del seed_w
    phases["first_steps"] = H.clock() - t
    H.say("fingerprints", H.program_fingerprints())
    H.say("flash_tilings", H.flash_tilings())

    # the window
    tokens_per_step = traffic["batch"] * traffic["seq"]
    tracer = ctx["tracer"]
    before = counter.snapshot()
    tracer.start()
    setup_s = H.clock() - ctx["t0"]
    t_open = H.clock()
    ends, losses, k, pending = [], [], n_check, None
    while True:
        started = H.clock()
        if started - t_open >= args.seconds and k > n_check:
            break
        k += 1
        with tracer.span("train.step"):
            loss = step(*to_device(T.train_batch(traffic, vocab, seed, k)))
            if pending is not None:         # one step in flight, no more
                pending._data.block_until_ready()
                ends.append(H.clock())
        losses.append(loss)
        pending = loss
    pending._data.block_until_ready()
    ends.append(H.clock())
    tracer.stop()
    in_window = counter.since(before)
    steps = len(ends)
    elapsed = ends[-1] - t_open
    step_ms = np.diff([t_open] + ends) * 1e3
    losses = [float(x) for x in losses]
    peak = H.memory_peak_bytes(ctx["devices"])
    H.say("setup_phases_s", {k_: round(v, 3) for k_, v in phases.items()})
    H.say("step_times", H.step_stats(step_ms[1:] if steps > 1 else step_ms))
    H.say("compiles_in_window", in_window)
    if in_window["compiled"]:
        raise SystemExit(f"chipbench: {in_window['compiled']} programs "
                         f"compiled inside the window")

    # free the program, then the reference follows the first steps
    opt_cfg = cfg["runner"]["optimizer"]
    del model, opt, step, loss, pending
    gc.collect()
    jax.clear_caches()
    t = H.clock()
    from . import reference as R
    batches = [T.train_batch(traffic, vocab, seed, k_)
               for k_ in range(1, n_check + 1)]
    ref = R.train_steps(cfg, seed, batches, opt_cfg)
    numbers, leaves = numbers_for(prog, ref, ctx["cell_file"]["limits"])
    finite = all(np.isfinite(losses))
    numbers["window_loss_nonfinite"] = {"value": 0.0 if finite else 1.0,
                                        "limit": 0.0}
    H.say("worst_leaves", leaves)
    if args.control:
        ctl = R.train_steps(cfg, seed, batches, opt_cfg, precision="fp8")
        H.say("control", {k: v["value"] for k, v in numbers_for(
            ctl, ref, ctx["cell_file"]["limits"])[0].items()})
    H.say("reference_s", round(H.clock() - t, 3))

    measured = {
        "train_tokens_per_s": steps * tokens_per_step / elapsed,
        "setup_s": setup_s,
    }
    ctx["finish"](correct=H.judge(numbers), attempted=steps + n_check,
                  failed=0 if finite else 1, measured=measured,
                  numbers=numbers, peak=peak,
                  run={"steps": steps, "elapsed_s": elapsed,
                       "step_ms": step_ms, "tokens_per_step": tokens_per_step,
                       "seq": traffic["seq"], "batch": traffic["batch"]})
