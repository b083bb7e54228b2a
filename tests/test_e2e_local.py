"""End-to-end: in-process master + real worker subprocesses training
synthetic MNIST over gRPC — the minimum slice of SURVEY §7, as a test.

Mirrors the reference's minikube integration tests (SURVEY §4) at process
granularity: real process boundaries, real wire traffic, no mocks.
"""

import os
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.client.local import free_port
from tests.jobs import run_job


def job_config(tmp_path, num_workers=1, **overrides):
    base = dict(
        job_name="e2e",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="mnist.mnist_cnn.custom_model",
        model_params={"learning_rate": 0.01},
        training_data="synthetic://mnist?n=400&shards=4",
        validation_data="synthetic://mnist?n=96&shards=2",
        records_per_task=100,
        minibatch_size=32,
        num_epochs=1,
        evaluation_steps=0,           # eval at epoch end
        num_workers=num_workers,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=1.0,
        task_timeout_s=120.0,
        shuffle=False,
    )
    base.update(overrides)
    return JobConfig(**base)


def test_local_job_end_to_end(tmp_path):
    cfg = job_config(tmp_path, num_workers=1)
    master, manager, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 400 records / 100 per task
    assert counts["failed_permanently"] == 0
    # epoch-end eval ran and aggregated
    results = master.evaluation.latest_results()
    assert "accuracy" in results and "loss" in results, results
    assert master.servicer.mean_training_loss() is not None
    # workers exited cleanly on job completion
    deadline = time.time() + 30
    while not manager.all_exited() and time.time() < deadline:
        time.sleep(0.5)
    assert manager.all_exited()


def test_local_job_with_grouped_dispatch(tmp_path):
    """--steps_per_dispatch=2: the worker runs batch groups through
    train_many (one XLA dispatch per 2 minibatches) and the job completes
    with identical task accounting — 100-record tasks at minibatch 32 leave
    a 4-batch task = 2 full groups, exercising group flush + the
    partial-group fallback on the final 4-record batch... (4 batches: 32,32,
    32,4 → one full group + one partial)."""
    cfg = job_config(tmp_path, num_workers=1, steps_per_dispatch=2,
                     wire_dtype="bfloat16")  # grouped path must honor the cast
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4
    assert counts["failed_permanently"] == 0
    # all 400 records were applied exactly once (grouped accounting)
    assert master.servicer.mean_training_loss() is not None
    results = master.evaluation.latest_results()
    assert "accuracy" in results, results


@pytest.mark.slow
def test_profiling_and_step_time_summaries(tmp_path):
    """Round-3 observability (SURVEY §5 tracing): --profile_dir produces
    jax.profiler trace files, and the master's train summary stream carries
    per-step wall time alongside loss.

    Marked slow for its ~20 s (measured on jax 0.9.0, PR 21): tier-1 runs
    close to its time limit."""
    cfg = job_config(
        tmp_path,
        profile_dir=str(tmp_path / "profile"),
        profile_start_step=2,
        profile_steps=4,
        summary_dir=str(tmp_path / "summaries"),
        job_type="training_only",
    )
    run_job(cfg, tmp_path)

    # trace files appeared (jax.profiler writes plugins/profile/<ts>/...)
    trace_files = []
    for root, _dirs, files in os.walk(tmp_path / "profile"):
        trace_files += [os.path.join(root, f) for f in files]
    assert trace_files, "profile_dir is empty — no trace was written"

    # the train summary stream has step_time_ms on every loss line
    import json

    events_path = tmp_path / "summaries" / "train" / "events.jsonl"
    lines = [
        json.loads(l) for l in events_path.read_text().splitlines() if l.strip()
    ]
    assert lines, "no train summaries written"
    assert all("step_time_ms" in rec and rec["step_time_ms"] > 0 for rec in lines)
    assert all("loss" in rec for rec in lines)


def test_local_transformer_lm_job_end_to_end(tmp_path):
    """The control plane is model-agnostic: the transformer LM (net-new
    family) runs the SAME master/worker job path the tabular models use —
    synthetic bigram shards in, tasks leased/retired exactly once, epoch-
    end eval aggregating token accuracy."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.transformer_lm.custom_model",
        model_params={
            "vocab": 32, "num_layers": 1, "dim": 32, "heads": 4,
            "max_len": 32, "seq_parallel": "none",
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=512&shards=4&vocab=32&seq_len=16",
        validation_data="synthetic://lm?n=64&shards=1&vocab=32&seq_len=16",
        records_per_task=128,
        minibatch_size=16,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 512 / 128
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert "token_accuracy" in results, results
    assert 0.0 <= results["token_accuracy"] <= 1.0


def test_local_olmoe_job_end_to_end(tmp_path):
    """OLMoE (dropless top-k experts, two auxiliary losses sown with their
    own coefficients) through the same master/worker path, grouped
    dispatch included: what `benchmark`'s job on the chip runs at width."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.olmoe.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 32,
            "num_experts": 8, "num_experts_per_tok": 2,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert master.servicer.mean_training_loss() < 7.0     # ln 256 = 5.5, + aux


def test_local_nemotron_h_job_end_to_end(tmp_path):
    """Nemotron-H (Mamba-2 mixers, a held share of sigmoid-routed relu²
    experts, grouped-query attention; the routers' selection bias riding in
    `extra_vars` through every step and task) through the same master/worker
    path: what the benchmark's cell runs at width, as a job."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.nemotron_h.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 48, "num_hidden_layers": 5,
            "hybrid_override_pattern": "ME*ME", "mamba_num_heads": 8,
            "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
            "chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "n_routed_experts": 4, "router_experts": 16,
            "first_expert": 4, "num_experts_per_tok": 3,
            "moe_intermediate_size": 24,
            "moe_shared_expert_intermediate_size": 40,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert master.servicer.mean_training_loss() < 6.0     # ln 256 = 5.5, no aux


def test_local_glm4_moe_lite_job_end_to_end(tmp_path):
    """GLM-4.7-Flash's block (latent attention, a dense layer, a held share
    of sigmoid-routed gated-SiLU experts, the multi-token-prediction module:
    `outputs` a dict of two logit streams, the loss a dict of its terms)
    through the same master/worker path, evaluation included."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.glm4_moe_lite.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 48, "num_hidden_layers": 3,
            "first_k_dense_replace": 1, "intermediate_size": 96,
            "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "n_routed_experts": 4, "router_experts": 16, "first_expert": 4,
            "num_experts_per_tok": 3, "moe_intermediate_size": 24,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert 0.0 <= results["mtp_token_accuracy"] <= 1.0
    # ln 256 = 5.5 for each stream: main + 0.3 x the module's
    assert master.servicer.mean_training_loss() < 1.3 * 6.0


def test_local_mellum_job_end_to_end(tmp_path):
    """Mellum2's block (three sliding-window layers to one full layer under
    two rotary tables, 4/2 grouped-query heads, a held share of softmax-routed
    experts with renormalised weights, the loss a dict beside a sown auxiliary
    term) through the same master/worker path, evaluation included; the
    window (8 keys) is shorter than the sequence (32)."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.mellum.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 48, "num_hidden_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "sliding_window": 8, "original_max_position_embeddings": 16,
            "num_experts": 4, "router_experts": 16, "first_expert": 4,
            "num_experts_per_tok": 3, "moe_intermediate_size": 24,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    assert 0.0 <= master.evaluation.latest_results()["token_accuracy"] <= 1.0
    # ln 256 = 5.5, and four layers' load-balance terms at 0.01 each
    assert master.servicer.mean_training_loss() < 6.0


def test_local_keye_vl2_job_end_to_end(tmp_path):
    """Keye-VL-2.0's block (a learned selection of 8 keys a query by a 3-head
    indexer with its own KL loss, 4/2 grouped-query heads with q/k norms, a
    held share of softmax-routed experts, two sown auxiliary terms reported by
    name) through the same master/worker path, evaluation included."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.keye_vl2.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 48, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "indexer_num_heads": 3, "indexer_head_dim": 8, "index_topk": 8,
            "num_experts": 4, "router_experts": 16, "first_expert": 4,
            "num_experts_per_tok": 3, "moe_intermediate_size": 24,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    assert 0.0 <= master.evaluation.latest_results()["token_accuracy"] <= 1.0
    # ln 256 = 5.5, two layers' index losses (each well under one at the
    # seed) and their load-balance terms at 0.001 each
    assert master.servicer.mean_training_loss() < 7.0


def test_run_job_stops_when_the_job_is_dead(tmp_path):
    """The harness itself (tests/jobs.py): a one-process worker started as
    cohort member 2 of 1 dies at world formation on every launch. run_job
    must raise once the relaunch budget is spent — about four launches —
    with the worker's own words, not wait out its 420 s deadline."""
    cfg = job_config(tmp_path)
    t0 = time.time()
    with pytest.raises(AssertionError, match="world formation failed"):
        run_job(cfg, tmp_path, extra_env={"EDL_PROCESS_ID": "2"})
    assert time.time() - t0 < 90
