"""chip_smoke.py on a machine without a chip: it must refuse to pass, its
parent must stay off JAX, and its phases must still be runnable code — here
at a tiny size on the CPU, kernels in interpret mode."""

import os
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


def test_parent_and_launcher_do_not_import_jax():
    """A process that has touched JAX holds the chip its child needs."""
    subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke, elasticdl_tpu.client.local\n"
         "assert 'jax' not in sys.modules, 'jax imported'"],
        cwd=REPO_ROOT, check=True, timeout=120)


def test_script_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_phases_run_tiny_on_cpu(tmp_path, monkeypatch):
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    tiny = chip_smoke.TINY

    assert chip_smoke.probe_devices()["platform"] == "cpu"

    job = chip_smoke.run_job("tiny_job", tiny, "cpu", str(tmp_path), 300)
    assert job["devices"]["platform"] == "cpu"
    assert job["tasks"]["finished_training"] == 2
    assert job["spawn_to_first_dispatch_s"] > 0

    monkeypatch.setenv("EDL_FLASH", "1")    # flash route off-TPU (interpreted)
    with interpret_mode():
        kernels = chip_smoke.check_kernels(tiny, require_mosaic=False)
    assert kernels["placement_err"] <= chip_smoke.PLACEMENT_TOL
    assert max(kernels["flash_err"].values()) <= chip_smoke.FLASH_TOL
    with pytest.raises(RuntimeError, match="no Mosaic custom call"):
        chip_smoke.check_kernels(tiny)      # the CPU must not pass as a chip

    monkeypatch.setattr(chip_smoke, "_bytes_in_use", lambda device: 1)
    assert chip_smoke.check_shards(tiny)["shard_shape"] == [13312 // 4, 17]

    # outside interpret mode: the kernel inside shard_map never returns here
    lookup = chip_smoke.check_lookup(tiny)
    assert lookup["routed"]["fullest"] <= lookup["cap"] < (
        lookup["overflow"]["fullest"])


def test_interpret_mode_on_a_tpu_backend_is_an_error(monkeypatch):
    import jax.numpy as jnp

    from elasticdl_tpu.ops import embedding, pallas_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 16, 1, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret mode"):
        pallas_attention.flash_attention(q, q, q, interpret=True)
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret mode"):
        pallas_attention.flash_attention(q, q, q)
    ids = jnp.zeros((8192,), jnp.int32)
    with pytest.raises(RuntimeError, match="interpret mode"):
        embedding.scatter_add_dense(ids, jnp.zeros((8192, 8)), 8192)


def test_cohort_members_are_given_disjoint_chips(monkeypatch):
    """One process per chip: on the four-chip host four cohort members get
    one chip each through libtpu's variables; a split the host cannot make
    is named in the log, and a CPU box gets nothing."""
    from elasticdl_tpu.master import process_manager as pm

    ports = [7001, 7002, 7003, 7004]
    assert pm.tpu_process_env(0, 4, ports) == {}            # no TPU here
    monkeypatch.setattr(pm, "local_tpu_chips", lambda: 4)
    envs = [pm.tpu_process_env(p, 4, ports) for p in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    assert pm.tpu_process_env(0, 1, [7001]) == {}           # one owner: all
    errors = []
    monkeypatch.setattr(pm.logger, "error", lambda *a: errors.append(a))
    assert pm.tpu_process_env(0, 2, [7001, 7002]) == {}     # not brought up
    assert "cannot each own chips" in errors[0][0]
