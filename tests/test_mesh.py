"""Runtime SPMD mesh layer (paddle_tpu.distributed.mesh).

The acceptance bars:
  * a 2x2 ``(fsdp, tensor)`` mesh train step is LOSS-EXACT (bitwise)
    vs the same model fused-stepped on one device — ZeRO-3 storage
    sharding with gather-at-use changes placement, not math;
  * the runtime SH/MEM gate refuses bad programs BEFORE compile with
    the same finding codes the static plane prints (SH201 divisibility,
    MEM301 HBM budget);
  * the per-chip live bytes XLA's buffer assignment reports for the
    compiled step agree with ``analysis/memory.py``'s prediction
    (state within 10%; the liveness-walk peak stays a sound upper
    bound);
  * ``MeshRuntime.describe()`` round-trips through
    ``tools/shard_check.py --from-runtime`` — CI lints the specs that
    RUN, not a mirror.

The multi-process 2x2 gloo drill lives in
``test_multiprocess_mesh_train_loss_exact`` (2 real processes x 2 CPU
devices via the launch CLI — fsdp crosses the process boundary — each
rank checking the sharded losses against its own local single-device
reference).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as optim
from paddle_tpu import jit as jit_mod
from paddle_tpu.distributed.mesh import (MeshProgramRejected, MeshRuntime,
                                         TPMemberDied)

from greedy_ref import greedy_ref

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARD_CHECK = os.path.join(REPO, "tools", "shard_check.py")
MESH_AXES = {"data": 1, "fsdp": 2, "tensor": 2}
STEPS = 5


def _make_llama(seed=7):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=176,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _build_step(model, plan):
    opt = optim.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def fn(ids, labels):
        out = model(ids)
        logits = out[0] if isinstance(out, (tuple, list)) else out
        return paddle.nn.functional.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))

    return jit_mod.TrainStep(fn, opt, mesh_plan=plan)


def _batch():
    rng = np.random.RandomState(0)
    return (paddle.to_tensor(rng.randint(0, 128, size=(2, 16))),
            paddle.to_tensor(rng.randint(0, 128, size=(2, 16))))


def _losses(step, n=STEPS):
    ids, labels = _batch()
    out = []
    for _ in range(n):
        loss = step(ids, labels)
        out.append(float(np.asarray(loss._data)))
    return out


@pytest.fixture(scope="module")
def sharded_run():
    """One compiled 2x2 mesh train run + its single-device reference
    (module-scoped: the exactness, memory and describe tests share the
    two compiles instead of paying them three times)."""
    base = _losses(_build_step(_make_llama(), None))
    rt = MeshRuntime(MESH_AXES)
    plan = rt.train_plan(budget_gib=16.0)
    step = _build_step(_make_llama(), plan)
    sharded = _losses(step)
    return {"rt": rt, "plan": plan, "step": step,
            "base": base, "sharded": sharded}


# -- mesh construction + spec policies ---------------------------------------

@pytest.mark.quick
def test_runtime_axes_and_spec_policies():
    rt = MeshRuntime(MESH_AXES)
    assert rt.size == 4 and rt.axes == MESH_AXES
    assert tuple(rt.mesh.axis_names) == ("data", "fsdp", "tensor")
    # plan policy: 2D dim0 -> fsdp, trailing divisible dim -> tensor
    assert rt.train_param_spec((8, 4), "w") == ("fsdp", "tensor")
    # norms/1D replicate
    assert rt.train_param_spec((8,), "ln1") == (None,)
    # serving: column-parallel only (trailing dim), vectors replicate
    assert rt.serving_weight_spec((8, 4)) == (None, "tensor")
    assert rt.serving_weight_spec((8,)) == (None,)
    # batch dim0 over data axes when divisible, else replicated
    rt2 = MeshRuntime({"data": 2, "fsdp": 2})
    assert rt2.batch_spec((4, 16)) == ("data", None)
    assert rt2.batch_spec((3, 16)) == (None, None)
    with pytest.raises(ValueError, match="unknown mesh axes"):
        MeshRuntime({"pipeline": 2})
    with pytest.raises(ValueError, match="device"):
        MeshRuntime({"data": 1024})


@pytest.mark.quick
def test_runtime_gate_refuses_with_static_finding_codes():
    rt = MeshRuntime(MESH_AXES)
    # SH201: declared shard dim does not divide
    with pytest.raises(MeshProgramRejected, match="SH201") as ei:
        rt.gate_specs([("w", (7, 5), ("fsdp", None))])
    assert {f.rule for f in ei.value.findings} == {"SH201"}
    # MEM301: predicted bytes over the HBM budget
    with pytest.raises(MeshProgramRejected, match="MEM301") as ei:
        rt.gate_memory(predicted_bytes=2.0 * 1024 ** 3, budget_gib=1.0)
    assert {f.rule for f in ei.value.findings} == {"MEM301"}


def test_mem301_refuses_train_step_before_compile():
    plan = MeshRuntime(MESH_AXES).train_plan(budget_gib=1e-6)
    step = _build_step(_make_llama(), plan)
    ids, labels = _batch()
    with pytest.raises(MeshProgramRejected, match="MEM301"):
        step(ids, labels)


# -- the exactness bar -------------------------------------------------------

def test_sharded_train_step_loss_exact_vs_single_device(sharded_run):
    base, sharded = sharded_run["base"], sharded_run["sharded"]
    assert len(sharded) == STEPS
    assert sharded == base, (
        f"2x2 mesh drifted from single device:\n{base}\nvs\n{sharded}")
    comm = sharded_run["plan"].collective_bytes_by_axis()
    assert comm.get("fsdp", 0) > 0 and comm.get("tensor", 0) > 0, comm


# -- runtime <-> static memory cross-check -----------------------------------

def test_mesh_memory_report_two_sided(sharded_run):
    ids, labels = _batch()
    rep = sharded_run["step"].mesh_memory_report(ids, labels)
    assert rep["within_tolerance"], rep       # state agrees within 10%
    assert rep["peak_bound_sound"], rep       # walk never under-predicts
    assert 0 < rep["measured_state_bytes"] <= rep["measured_peak_bytes"]


# -- describe() -> shard_check --from-runtime --------------------------------

def test_describe_round_trips_through_shard_check(sharded_run, tmp_path):
    rt, plan = sharded_run["rt"], sharded_run["plan"]
    dump = rt.describe(train_plan=plan)
    assert dump["kind"] == "mesh_runtime" and dump["mesh"] == MESH_AXES
    assert dump["params"] and "memory" in dump
    path = tmp_path / "runtime_dump.json"
    path.write_text(json.dumps(dump))

    ok = subprocess.run(
        [sys.executable, SHARD_CHECK, "--from-runtime", str(path), "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    out = json.loads(ok.stdout)
    assert out["mode"] == "from-runtime" and not out["findings"]

    over = subprocess.run(
        [sys.executable, SHARD_CHECK, "--from-runtime", str(path),
         "--hbm-gib", "1e-6"],
        capture_output=True, text=True, cwd=REPO)
    assert over.returncode == 1 and "MEM301" in over.stdout, over.stdout


# -- hapi wiring -------------------------------------------------------------

def test_hapi_prepare_with_mesh_plan_loss_exact():
    from paddle_tpu import nn
    from paddle_tpu.hapi import Model

    def build():
        paddle.seed(11)
        return Model(nn.Sequential(nn.Linear(8, 32), nn.ReLU(),
                                   nn.Linear(32, 2)))

    rng = np.random.RandomState(2)
    x = rng.randn(4, 8).astype(np.float32)
    y = rng.randint(0, 2, size=(4,)).astype(np.int64)

    def run(plan):
        m = build()
        m.prepare(optimizer=optim.AdamW(learning_rate=1e-2,
                                        parameters=m.parameters()),
                  loss=nn.CrossEntropyLoss(), jit=True, plan=plan)
        return [float(np.asarray(m.train_batch([x], [y])[0]))
                for _ in range(3)]

    base = run(None)
    plan = MeshRuntime(MESH_AXES).train_plan(budget_gib=16.0)
    assert run(plan) == base

    m = build()
    with pytest.raises(ValueError, match="requires jit=True"):
        m.prepare(optimizer=optim.AdamW(learning_rate=1e-2,
                                        parameters=m.parameters()),
                  loss=nn.CrossEntropyLoss(), plan=plan)


# -- serving shard group -----------------------------------------------------

@pytest.fixture()
def gpt_batcher():
    from paddle_tpu.inference.serving import ContinuousBatcher
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    lm = GPT2ForCausalLM(cfg)
    lm.eval()
    return ContinuousBatcher(lm, compile=False, max_batch=2, s_max=64)


def test_shard_serving_token_exact_and_member_death(gpt_batcher):
    lm = gpt_batcher.model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 128, size=n).astype(np.int64) for n in (5, 9)]
    refs = [greedy_ref(lm, p, 8) for p in prompts]

    group = MeshRuntime({"tensor": 2}).shard_serving(gpt_batcher,
                                                     group_name="g0")
    assert gpt_batcher.shard_group is group and group.degree == 2
    assert group.placed_params["transformer.wte.weight"]["spec"] == \
        [None, "tensor"]
    rids = [gpt_batcher.submit(p, max_new_tokens=8) for p in prompts]
    while gpt_batcher.active or gpt_batcher.pending:
        gpt_batcher.step()
    for rid, ref in zip(rids, refs):
        assert np.array_equal(np.asarray(gpt_batcher.result(rid)), ref)

    # a dead member makes the group unsteppable — non-retryable by design
    group.fail_member(group.members[0], reason="drill")
    with pytest.raises(TPMemberDied, match="g0"):
        gpt_batcher.step()
    from paddle_tpu.resilience.retry import DEFAULT_RETRYABLE
    assert not issubclass(TPMemberDied, DEFAULT_RETRYABLE)


def test_shard_serving_refuses_indivisible_heads(gpt_batcher):
    with pytest.raises(MeshProgramRejected, match="SH201"):
        MeshRuntime({"tensor": 8}).shard_serving(gpt_batcher)


# -- the multi-process drill -------------------------------------------------

def test_multiprocess_mesh_train_loss_exact(tmp_path):
    """2 REAL processes x 2 CPU devices each form a 2x2 (fsdp, tensor)
    gloo mesh — the fsdp (ZeRO-3 gather) axis crosses the process
    boundary, tensor stays intra-process — and train the small llama 5
    fused steps; every rank asserts the sharded losses are
    bitwise-identical to its own local single-device reference run."""
    worker = os.path.join(REPO, "tests", "helpers", "mp_mesh_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_MESH_SHAPE"] = "data:1,fsdp:2,tensor:2"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         worker],
        capture_output=True, text=True, timeout=420, cwd=REPO, env=env)
    logs = ""
    log_root = tmp_path / "logs"
    if log_root.exists():
        for f in sorted(log_root.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()
    assert proc.returncode == 0, (
        f"launch failed rc={proc.returncode}\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}\nlogs:{logs[-4000:]}")
    marks = [ln for ln in logs.splitlines() if "MPMESH_OK" in ln]
    for r in range(2):
        assert any(f"MPMESH_OK rank={r}/2" in ln for ln in marks), (
            f"rank {r} did not finish\n{logs[-4000:]}")
    # every rank converged on the SAME loss trajectory
    assert len({ln.split("losses=")[1] for ln in marks}) == 1, marks


@pytest.mark.slow
@pytest.mark.ckpt
def test_multiprocess_elastic_checkpoint_survives_rank_kill(tmp_path):
    """Save under a process-spanning 2x2 mesh, chaos-kill rank 1 mid
    shard write on the NEXT save (rank 0 must time out on the missing
    ack and leave the step torn), then restart as ONE process on ONE
    device: the restore must fall back to the committed step with a
    typed torn_step finding and continue on the 2x2 world's exact loss
    trajectory."""
    worker = os.path.join(REPO, "tests", "helpers", "mp_ckpt_worker.py")
    root = str(tmp_path / "ckpt")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_MESH_SHAPE"] = "data:1,fsdp:2,tensor:2"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["MP_CKPT_ROOT"] = root
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])

    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         worker],
        capture_output=True, text=True, timeout=420, cwd=REPO, env=env)
    logs = ""
    log_root = tmp_path / "logs"
    if log_root.exists():
        for f in sorted(log_root.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()
    assert proc.returncode == 0, (
        f"launch failed rc={proc.returncode}\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}\nlogs:{logs[-4000:]}")
    saves = [ln for ln in logs.splitlines() if "MPCKPT_SAVE_OK" in ln]
    assert any("rank=0/2" in ln for ln in saves), logs[-4000:]
    assert any("MPCKPT_TORN rank=0 step=4" in ln
               for ln in logs.splitlines()), logs[-4000:]
    # the loss the 2x2 world computed right after the committed save
    ref_losses = json.loads(saves[0].split("losses=")[1])
    ref_step4 = ref_losses[3]

    # the torn step is on disk exactly as the crash left it; the
    # offline inspector must flag it and still name step 3 sound
    ins = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ckpt_inspect.py"),
         root, "--json"], capture_output=True, text=True, timeout=60,
        cwd=REPO)
    assert ins.returncode == 2, ins.stdout + ins.stderr
    report = json.loads(ins.stdout)
    assert report["latest_sound"] == 3, report

    env_r = dict(env)
    env_r.pop("PADDLE_MESH_SHAPE")
    env_r["MP_CKPT_PHASE"] = "restore"
    proc_r = subprocess.run([sys.executable, worker], capture_output=True,
                            text=True, timeout=420, cwd=REPO, env=env_r)
    assert proc_r.returncode == 0, (
        f"restore phase rc={proc_r.returncode}\n"
        f"stdout:{proc_r.stdout[-2000:]}\nstderr:{proc_r.stderr[-2000:]}")
    restored = [ln for ln in proc_r.stdout.splitlines()
                if "MPCKPT_RESTORE_OK" in ln]
    assert restored and "torn_step" in restored[0], proc_r.stdout[-2000:]
    got_step4 = json.loads(restored[0].split("losses=")[1])[0]
    assert got_step4 == ref_step4, (
        f"elastic restart diverged: {got_step4!r} vs the 2x2 world's "
        f"{ref_step4!r}")
