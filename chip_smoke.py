"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python3 chip_smoke.py            # every phase the visible chips allow
    python3 chip_smoke.py --phases 0,C

Drives the normal training path once on a TPU, at the full width of DeepFM,
and checks what comes out by the repo's own means. Exits non-zero, printing
no result line, when JAX finds no TPU or when any phase fails; on success
the last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.

This parent never imports jax: a process that has touched JAX holds the
chip, so each phase is a child process, one after another, each started with
`JAX_PLATFORMS=tpu` (a TPU that fails to initialise is then an error in the
child, not a CPU run).

  0  probe    platform / device_kind / count / versions; rebuilds the native
              batch parser from batch_parse.cc on this machine.
  A  job      `python -m elasticdl_tpu.client.main train` — master →
              ProcessManager → worker.main → Trainer — DeepFM at the zoo's
              defaults, 16 steps, epoch-end evaluation (AUC), one checkpoint.
  B  kernels  Trainer directly: the compiled DeepFM step holds the Mosaic
              placement kernel and agrees with the XLA scatter; flash
              attention agrees with the XLA attention; transformer_lm steps.
  C  four chips (only when four are visible; otherwise reported as not
              run): the job of A — 8 steps, each costs four chips — on
              data=4 and on data=2,model=2, the table's shards on four
              distinct devices, and the manual lookup on data=4 against
              numpy on BOTH schedules: ids every shard owns its share of
              (ids and rows exchanged all-to-all) and ids one shard owns too
              many of (the overflow branch, the gathered schedule), the
              placement kernel under `shard_map` in each; and of the job's
              compiled step on data=4, how many table-sized instructions that
              are not the lookup's own stand in the lookup's conditional
              branches (`table_sized_ops_in_cond`: 0). Several worker
              processes on one host are reported as not brought up (ROADMAP
              A3), not attempted.

Logs land in chiprun_out/chip_smoke/; checkpoints go to a temporary
directory that is removed on exit.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time
from datetime import datetime

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
BUDGET_S = 1140.0          # the contract allows 1200 s, compilation included
PHASE_CAP_S = 600.0        # no phase may take the whole budget by hanging
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'

# Full width: the zoo's own DeepFM (26 x 100k-row fields -> 2.6M rows x 17
# columns, hidden 400,400, bf16 tower, manual embedding). At batch 8192 the
# backward places 213k ids into 2.6M rows — past every gate of the Pallas
# route (ops/embedding.py). TINY keeps the same routes alive in interpret
# mode for the CPU test (tests/test_chip_smoke.py).
FULL = dict(
    model_params="",                      # zoo defaults
    field_vocab=100_000, batch=8192, steps=16, steps_per_dispatch=4,
    eval_records=32768,
    attn=(8, 1024, 8, 64),                # batch, T, heads, head_dim (bf16)
    lm=dict(vocab=8192, num_layers=4, dim=512, heads=8, max_len=1024),
    lm_batch=(8, 1024),
)
FULL_C = dict(FULL, steps=8)
TINY = dict(
    model_params="field_vocab=512;hidden=32,32",
    field_vocab=512, batch=256, steps=8, steps_per_dispatch=4,
    eval_records=1024,
    attn=(2, 128, 2, 64),
    lm=dict(vocab=256, num_layers=1, dim=64, heads=2, max_len=128),
    lm_batch=(2, 128),
)

# Tolerances, each with its reason.
# Placement kernel vs XLA scatter, |err| over max|ref|: the MXU runs bf16,
# and the kernel splits every f32 cotangent into two bf16 terms (hi + lo),
# keeping 16 mantissa bits -> 2^-17 ~ 7.6e-6 per contribution.
PLACEMENT_TOL = 1e-5
# Flash vs XLA attention on bf16 inputs, |err| over max|ref|: both round
# their output to bf16 (2^-8) and the XLA branch also rounds the
# probabilities to bf16 — tests/test_pallas_attention.py's bf16 tolerance.
FLASH_TOL = 3e-2


# ---------------------------------------------------------------------- #
# parent side: children, one at a time


def _run_child(name, argv, env, timeout_s):
    """Run one child to completion in its own process group, output to
    OUT_DIR/<name>.log. Raises on a non-zero exit or a timeout; the whole
    group is killed either way, so nothing the child started outlives it."""
    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, f"{name}.log")
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=max(1.0, timeout_s))
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    seconds = time.monotonic() - t0
    with open(log_path, errors="replace") as f:
        text = f.read()
    if rc != 0:
        sys.stderr.write(text[-6000:])
        raise RuntimeError(
            f"phase {name} " + (f"timed out after {timeout_s:.0f}s"
                                if rc is None else f"exited {rc}")
            + f"; log: {log_path}")
    return text, seconds


def _child_env(platform):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _phase_child(name, phase, timeout_s):
    """One of this file's child phases on the TPU; its last line is JSON."""
    text, seconds = _run_child(
        name, [sys.executable, os.path.abspath(__file__), "--child", phase],
        _child_env("tpu"), timeout_s)
    result = json.loads(text.strip().splitlines()[-1])
    result["seconds"] = round(seconds, 1)
    return result


_STAMP = re.compile(r"\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\]")


def _stamp(line):
    m = _STAMP.search(line)
    t = datetime.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    return t.timestamp() + int(m.group(2)) / 1e3


def run_job(name, size, platform, work_dir, timeout_s, mesh_shape="",
            data_shards=1):
    """Phase A (and C's jobs): one training job through the user's entry
    point, checked from what master and worker logged."""
    batch = size["batch"] * data_shards
    # one dispatch per task, one task per data shard (tasks do not span them)
    per_task = batch * size["steps_per_dispatch"]
    want_tasks = size["steps"] // size["steps_per_dispatch"]
    argv = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train",
        "--job_name", name,
        "--model_zoo", os.path.join(ROOT, "model_zoo"),
        "--model_def", "deepfm.deepfm.custom_model",
        "--model_params", size["model_params"],
        "--minibatch_size", str(batch),
        "--steps_per_dispatch", str(size["steps_per_dispatch"]),
        "--training_data",
        f"synthetic://criteo?n={batch * size['steps']}&shards={want_tasks}",
        "--validation_data",
        f"synthetic://criteo?n={size['eval_records'] * data_shards}&shards=1",
        "--records_per_task", str(per_task),
        "--num_epochs", "1",
        "--checkpoint_dir", os.path.join(work_dir, name, "ckpt"),
        "--master_addr", "localhost:0",
    ]
    if mesh_shape:
        argv += ["--mesh_shape", mesh_shape]
    log, seconds = _run_child(name, argv, _child_env(platform), timeout_s)
    lines = log.splitlines()

    def find(pattern):
        hits = [ln for ln in lines if re.search(pattern, ln)]
        if not hits:
            raise RuntimeError(f"{name}: no log line matches {pattern!r}")
        return hits

    # the worker's own statement of what it trained on
    devices = json.loads(
        find(r"training devices: \{")[0].split("training devices: ", 1)[1])
    if devices["platform"] != platform:
        raise RuntimeError(
            f"{name}: worker trained on {devices['platform']!r}, not "
            f"{platform!r}: {devices}")
    if "pure-python path" in log:
        raise RuntimeError(f"{name}: the native batch parser did not build")
    done = find(r"job finished: ")[-1]
    counts = re.search(r"job finished: (\{.*?\}) mean_loss=(\S+) eval=(\{.*\})",
                       done)
    # the master's own dict reprs; a nan in them fails to parse, as it should
    tasks = ast.literal_eval(counts.group(1))
    evals = ast.literal_eval(counts.group(3))
    mean_loss = float(counts.group(2))
    if (tasks["finished_training"] != want_tasks or tasks["todo"]
            or tasks["doing"] or tasks["failed_permanently"]):
        raise RuntimeError(f"{name}: tasks not all done: {tasks}")
    if not abs(mean_loss) < float("inf"):       # false for nan too
        raise RuntimeError(f"{name}: mean training loss {mean_loss}")
    if not 0.0 <= evals.get("auc", -1.0) <= 1.0:
        raise RuntimeError(f"{name}: evaluation reported no AUC: {evals}")
    find(r"checkpoint step %d -> " % size["steps"])
    find(r"worker 0 exited cleanly")       # exit code 0, says its manager
    out = {
        "devices": devices, "tasks": tasks,
        "mean_loss": mean_loss, "eval": evals, "seconds": round(seconds, 1),
    }
    # set-up facts, not metrics: the first task's figure holds the compile
    per_task = [float(re.search(r"([\d.]+) ms/step", ln).group(1))
                for ln in find(r"training task \d+: ")]
    out["spawn_to_first_dispatch_s"] = round(
        _stamp(find(r"training task \d+: ")[0])
        - _stamp(find(r"spawned worker 0 ")[0]), 1)
    out["steady_ms_per_step"] = statistics.median(per_task[1:])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default="0,A,B,C",
                    help="comma list out of 0,A,B,C (0 always runs)")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return _child_main(args.child)

    phases = set(args.phases.upper().split(","))
    t0 = time.monotonic()

    def left():
        return min(PHASE_CAP_S, BUDGET_S - (time.monotonic() - t0))

    report = {}

    def passed(phase, result):
        report[phase] = result
        print(f"phase {phase} ok: {json.dumps(result)}", flush=True)

    def not_run(phase, why):            # by name, never silently passed
        report[phase] = why
        print(f"phase {phase} {why}", flush=True)

    work_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        probe = _phase_child("phase0_probe", "probe", 180)
        if probe["platform"] != "tpu":
            raise RuntimeError(f"no TPU found: JAX reports {probe}")
        passed("0", probe)
        if "A" in phases:
            passed("A", run_job("phaseA_job", FULL, "tpu", work_dir, left()))
        if "B" in phases:
            passed("B", _phase_child("phaseB_kernels", "kernels", left()))
        if "C" in phases and probe["device_count"] >= 4:
            passed("C data=4", run_job(
                "phaseC_data4", FULL_C, "tpu", work_dir, left(),
                mesh_shape="data=4", data_shards=4))
            passed("C data=2,model=2", run_job(
                "phaseC_data2_model2", FULL_C, "tpu", work_dir, left(),
                mesh_shape="data=2,model=2", data_shards=2))
            passed("C shards", _phase_child("phaseC_shards", "shards", left()))
            passed("C lookup", _phase_child("phaseC_lookup", "lookup", left()))
            not_run("C processes",
                    "NOT BROUGHT UP: several worker processes on one host "
                    "(--num_processes N) have not trained on chips; see "
                    "ROADMAP A3")
        elif "C" in phases:
            not_run("C", f"NOT RUN: needs four chips, "
                         f"{probe['device_count']} visible")
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    report["seconds"] = round(time.monotonic() - t0, 1)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"ok": True, "device": {
        "platform": probe["platform"], "kind": probe["device_kind"],
        "count": probe["device_count"]}}), flush=True)
    return 0


# ---------------------------------------------------------------------- #
# child side: the only code here that imports jax


def _child_main(phase):
    result = {"probe": probe_devices, "kernels": check_kernels,
              "shards": check_shards, "lookup": check_lookup}[phase](FULL)
    print(json.dumps(result), flush=True)
    return 0


def probe_devices(size=None):
    """Phase 0. Also builds libbatch_parse.so from its source HERE, so the
    worker of Phase A cannot be running a parser git does not hold."""
    from importlib import metadata

    import jax

    from elasticdl_tpu.data import nativelib

    try:
        devices = jax.devices()
    except RuntimeError as e:       # JAX_PLATFORMS=tpu and no TPU answers
        raise SystemExit(f"chip_smoke: no TPU found: {e}")
    if nativelib.load_shared("batch_parse", force_build=True) is None:
        raise RuntimeError("batch_parse.cc did not build (g++ missing?)")
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": metadata.version("jaxlib"),
        "libtpu": metadata.version("libtpu"),
    }


def _deepfm_trainer(size, mesh):
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = JobConfig.from_argv([
        "--model_zoo", os.path.join(ROOT, "model_zoo"),
        "--model_def", "deepfm.deepfm.custom_model",
        "--model_params", size["model_params"],
    ])
    return Trainer(ModelSpec.from_config(cfg), mesh)


def _deepfm_batch(size, batch, seed=0):
    import numpy as np

    r = np.random.RandomState(seed)
    return {
        "features": {
            "dense": r.rand(batch, 13).astype(np.float32),
            "cat": r.randint(0, 1 << 30, (batch, 26)).astype(np.int32),
        },
        "labels": r.randint(0, 2, (batch,)).astype(np.int32),
        "mask": np.ones((batch,), np.float32),
    }


def _scaled_err(got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        raise RuntimeError("non-finite values")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def check_kernels(size, require_mosaic=True):
    """Phase B: both Pallas kernels compiled by Mosaic inside real steps, and
    each against its XLA reference at the shape the repo trains at."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.common.runtime import configure_jax_runtime
    from elasticdl_tpu.ops import attention, embedding, pallas_attention
    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    configure_jax_runtime()
    mesh = build_mesh(devices=jax.devices()[:1])
    out = {}

    def mosaic_calls(exe):
        n = exe.as_text().count(MOSAIC_CALL)
        if require_mosaic and not n:
            raise RuntimeError(
                "compiled step holds no Mosaic custom call: the kernel was "
                "routed round (size gate, interpret mode, or no TPU)")
        return n

    # 1. the DeepFM step, lowered and compiled as the worker would
    trainer = _deepfm_trainer(size, mesh)
    batch = _deepfm_batch(size, size["batch"])
    state = trainer.init_state(batch)
    exe = trainer.aot_compile_train_step(state, batch)
    out["deepfm_mosaic_calls"] = mosaic_calls(exe)
    out["deepfm_step_gflops"] = round(
        trainer.train_step_cost(state, batch)["flops"] / 1e9, 3)
    state, logs = trainer.train_step(state, batch)
    if not np.isfinite(float(logs["loss"])):
        raise RuntimeError(f"DeepFM loss {float(logs['loss'])}")
    del state, exe

    # 2. table gradient: gather_rows' VJP (the placement kernel) vs
    # jnp.take's own (XLA's flat scatter-add), same ids and cotangents, at
    # the DeepFM shape (D = 16 + the linear column)
    rows = embedding.padded_vocab(26 * size["field_vocab"])
    r = np.random.RandomState(7)
    ids = jnp.asarray(r.randint(0, rows, (size["batch"], 26)), jnp.int32)
    ct = jnp.asarray(r.randn(size["batch"], 26, 17), jnp.float32)
    table = jnp.zeros((rows, 17), jnp.float32)

    def table_grad(take):
        fn = jax.jit(lambda t, i, c: jax.vjp(
            lambda t: take(t, i), t)[1](c)[0])
        exe = fn.lower(table, ids, ct).compile()
        return exe(table, ids, ct), exe

    got, exe = table_grad(embedding.gather_rows)
    out["placement_mosaic_calls"] = mosaic_calls(exe)
    ref, _ = table_grad(lambda t, i: jnp.take(t, i, axis=0))
    out["placement_err"] = _scaled_err(got, ref)
    if out["placement_err"] > PLACEMENT_TOL:
        raise RuntimeError(
            f"placement kernel off by {out['placement_err']:.3g} "
            f"(tolerance {PLACEMENT_TOL})")
    del got, ref, table, ct, exe

    # 3. flash attention, forward and backward, vs the XLA branch of
    # full_attention at full matmul precision
    b, t, h, d = size["attn"]
    r = np.random.RandomState(11)
    q, k, v = (jnp.asarray(r.randn(b, t, h, d), jnp.bfloat16)
               for _ in range(3))

    def fwd_bwd(attn):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
        exe = fn.lower(q, k, v).compile()
        (_, o), grads = exe(q, k, v)
        return (o, *grads), exe

    got, exe = fwd_bwd(
        lambda q, k, v: pallas_attention.flash_attention(q, k, v, causal=True))
    out["flash_mosaic_calls"] = mosaic_calls(exe)
    os.environ["EDL_FLASH"] = "0"                  # the XLA branch
    try:
        with jax.default_matmul_precision("highest"):
            ref, _ = fwd_bwd(
                lambda q, k, v: attention.full_attention(q, k, v, causal=True))
    finally:
        del os.environ["EDL_FLASH"]
    out["flash_err"] = {
        name: _scaled_err(g, x)
        for name, g, x in zip(("out", "dq", "dk", "dv"), got, ref)}
    if max(out["flash_err"].values()) > FLASH_TOL:
        raise RuntimeError(
            f"flash attention off: {out['flash_err']} (tolerance {FLASH_TOL})")

    # 4. the kernel inside a step: two steps of the zoo transformer
    from elasticdl_tpu.common.config import JobConfig

    cfg = JobConfig.from_argv([
        "--model_zoo", os.path.join(ROOT, "model_zoo"),
        "--model_def", "transformer.transformer_lm.custom_model",
        "--model_params",
        ";".join(f"{k}={v}" for k, v in size["lm"].items()),
    ])
    lm = Trainer(ModelSpec.from_config(cfg), mesh)
    bsz, seq = size["lm_batch"]
    toks = np.random.RandomState(13).randint(
        0, size["lm"]["vocab"], (bsz, seq)).astype(np.int32)
    lm_batch = {"features": toks, "labels": toks,
                "mask": np.ones((bsz,), np.float32)}
    lm_state = lm.init_state(lm_batch)
    out["lm_mosaic_calls"] = mosaic_calls(
        lm.aot_compile_train_step(lm_state, lm_batch))
    losses = []
    for _ in range(2):
        lm_state, logs = lm.train_step(lm_state, lm_batch)
        losses.append(float(logs["loss"]))
    if not np.isfinite(losses).all():
        raise RuntimeError(f"transformer_lm losses {losses}")
    out["lm_losses"] = [round(x, 4) for x in losses]
    return out


def _bytes_in_use(device):
    return device.memory_stats()["bytes_in_use"]


def check_shards(size):
    """Phase C: after Trainer.init_state on data=2,model=2 the table lives
    as four row shards on four distinct devices, dense parameters on all
    four, and every device reports memory in use."""
    import jax

    from elasticdl_tpu.common.runtime import configure_jax_runtime
    from elasticdl_tpu.ops import embedding
    from elasticdl_tpu.parallel.mesh import build_mesh

    configure_jax_runtime()
    devices = jax.devices()[:4]
    mesh = build_mesh({"data": 2, "model": 2}, devices)
    trainer = _deepfm_trainer(size, mesh)
    state = trainer.init_state(_deepfm_batch(size, size["batch"] * 2))
    table = state.params["fm_embedding"]["table"]
    rows = embedding.padded_vocab(26 * size["field_vocab"])
    shards = table.addressable_shards
    shapes = sorted({tuple(s.data.shape) for s in shards})
    owners = {s.device for s in shards}
    if len(shards) != 4 or owners != set(devices) or shapes != [(rows // 4, 17)]:
        raise RuntimeError(
            f"fm_embedding shards {shapes} on {len(owners)} device(s); "
            f"want four of {(rows // 4, 17)}")
    dense = state.params["dnn_0"]["kernel"]
    if {s.device for s in dense.addressable_shards} != set(devices):
        raise RuntimeError("dense parameters are not on all four devices")
    in_use = {str(d): _bytes_in_use(d) for d in devices}
    if not all(in_use.values()):
        raise RuntimeError(f"a device holds nothing: {in_use}")
    return {"shard_shape": list(shapes[0]), "bytes_in_use": in_use}


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\((.*)$")
_HLO_CALLEES = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
# they name or view an array that another instruction made
_HLO_FREE = ("parameter", "get-tuple-element", "tuple", "bitcast")


def hlo_computations(text):
    """A compiled program's text (`exe.as_text()`) as {computation:
    [instruction]}, an instruction a dict of its `name`, result `shape`
    (layout and all, a tuple's in parentheses), `opcode`, `op_name` (the
    scopes it was traced under, "" where it has none), the computations it
    `calls` (a fusion's body, a conditional's branches, a loop's two)."""
    computations, current = {}, None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            inst = _HLO_INSTRUCTION.match(line)
            if not inst:
                continue
            name, shape, opcode, rest = inst.groups()
            op_name = re.search(r'op_name="([^"]*)"', rest)
            current.append({
                "name": name, "shape": shape, "opcode": opcode,
                "op_name": op_name.group(1) if op_name else "",
                "calls": [c for one, group in _HLO_CALLEES.findall(rest)
                          for c in ([one] if one else
                                    re.findall(r"%([\w.\-]+)", group))]})
    return computations


def foreign_ops_in_lookup_branches(computations, shard_shape):
    """Of a program's `hlo_computations`: the instructions of `shard_shape`
    ("f32[425984,11]") that stand un-fused in a branch of the manual
    lookup's conditionals — those traced under `shard_map/`, the
    routed-or-overflow `cond` and `gather_rows`' guards inside it, and what
    their branches call — and are NOT the lookup's own: their `op_name` does
    not lie under `shard_map/`. What is the lookup's own there (the
    conditionals' results, the placement kernel's call, the flat branch's
    zeros and scatter) stays uncounted; an instruction XLA's conditional
    code motion carried in from outside — the optimizer's g * g before
    PR 61 — is a table-sized pass that fuses with nothing."""
    branches, seen = [], set()
    for insts in computations.values():
        for inst in insts:
            if inst["opcode"] == "conditional" and "shard_map/" in inst["op_name"]:
                branches += inst["calls"]
    found = []
    while branches:
        name = branches.pop()
        if name in seen:
            continue
        seen.add(name)
        for inst in computations.get(name, ()):
            if inst["opcode"] != "fusion":     # a fusion's body is fused
                branches += inst["calls"]
            if (inst["shape"].startswith(shard_shape)
                    and inst["opcode"] not in _HLO_FREE
                    and "shard_map/" not in inst["op_name"]):
                found.append(inst)
    return found


def check_lookup(size):
    """Phase C: rows and table gradient of the manual lookup on data=4
    against numpy, once on each of its schedules (ops/embedding.py): ids
    spread over the shards, which are exchanged all-to-all, and the same
    ids with the first device's all owned by shard 0, more than a bucket
    holds, which take the overflow branch. Which branch a step takes is
    `fullest > cap` by the code's own rule, recomputed here; the CPU tests
    tie the rule to the branch that runs. The routed schedule runs twice,
    for the two branches of the owners' lookups (`gather_rows`): uniform
    ids, more distinct a shard than the last of `distinct_caps` holds (the plain
    gather), and a field's ids Zipf over its own rows, as the benchmark's
    (each distinct row fetched once) — `distinct` is the most a shard's
    stream holds, sentinel included, again recomputed here.

    Then a fact of the program a job runs on this mesh, compiled and not
    run: in the zoo's DeepFM step, Adam and all, nothing table-sized that
    is not the lookup's own stands in the lookup's conditional branches
    (`table_sized_ops_in_cond`: 0; `foreign_ops_in_lookup_branches`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.common.runtime import configure_jax_runtime
    from elasticdl_tpu.ops import embedding
    from elasticdl_tpu.parallel.mesh import build_mesh

    configure_jax_runtime()
    mesh = build_mesh({"data": 4}, jax.devices()[:4])
    rows = embedding.padded_vocab(26 * size["field_vocab"])
    dim, batch = 17, 4 * size["batch"]
    cap = embedding.route_cap(size["batch"] * 26, 4)
    rng = np.random.default_rng(0)
    table_np = rng.standard_normal((rows, dim), dtype=np.float32)
    w_np = rng.standard_normal((batch, 26, dim), dtype=np.float32)
    spread = rng.integers(0, rows, (batch, 26), dtype=np.int32)
    piled = spread.copy()
    piled[:size["batch"]] %= rows // 4
    vocab = size["field_vocab"]
    ranks = np.minimum(rng.zipf(1.1, (batch, 26)) - 1, vocab - 1)
    skewed = (ranks * 40503 % vocab + np.arange(26) * vocab).astype(np.int32)

    def fn(table, ids, w):
        def total(t):
            return jnp.sum(embedding.embedding_lookup(t, ids) * w)

        return embedding.embedding_lookup(table, ids), jax.grad(total)(table)

    result = {"cap": cap}
    with jax.set_mesh(mesh):
        by_data = NamedSharding(mesh, P("data"))
        table = jax.device_put(table_np, by_data)
        w = jax.device_put(w_np, by_data)
        step = jax.jit(fn)
        for name, ids_np in (("routed", spread), ("routed_skewed", skewed),
                             ("overflow", piled)):
            fullest = max(int(np.bincount(src // (rows // 4)).max())
                          for src in ids_np.reshape(4, -1))
            if (fullest > cap) != (name == "overflow"):
                raise RuntimeError(
                    f"{name}: fullest bucket {fullest}, cap {cap}")
            # an owner's stream: the ids it owns and one sentinel
            distinct = 1 + max(
                np.unique(ids_np[ids_np // (rows // 4) == o]).size
                for o in range(4))
            room = embedding.distinct_caps(
                ids_np.size if name == "overflow" else 4 * cap)[-1]
            if name != "overflow" and (distinct <= room) != (
                    name == "routed_skewed"):
                raise RuntimeError(
                    f"{name}: {distinct} distinct ids a shard, room {room}")
            got, grad = step(table, jax.device_put(ids_np, by_data), w)
            if not np.array_equal(np.asarray(got), table_np[ids_np]):
                raise RuntimeError(f"{name}: looked-up rows differ")
            want = np.zeros_like(table_np)
            np.add.at(want, ids_np.reshape(-1), w_np.reshape(-1, dim))
            err = _scaled_err(grad, want)
            if err > PLACEMENT_TOL:
                raise RuntimeError(
                    f"{name}: table gradient err {err:.3g} > {PLACEMENT_TOL}")
            result[name] = {"fullest": fullest, "distinct": distinct,
                            "distinct_cap": room, "grad_err": err}
    trainer = _deepfm_trainer(size, mesh)
    step_batch = _deepfm_batch(size, batch)
    exe = trainer.aot_compile_train_step(
        trainer.abstract_train_state(step_batch), step_batch, abstract=True)
    foreign = foreign_ops_in_lookup_branches(
        hlo_computations(exe.as_text()), f"f32[{rows // 4},{dim}]")
    result["table_sized_ops_in_cond"] = len(foreign)
    if foreign:
        raise RuntimeError(
            "table-sized instructions moved into the lookup's conditional "
            f"branches: {[inst['name'] for inst in foreign]} "
            "(ops/embedding.py::_fence_cotangent)")
    return result


if __name__ == "__main__":
    raise SystemExit(main())
