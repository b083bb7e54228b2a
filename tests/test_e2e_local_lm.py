"""End-to-end, the language models: the control plane is model-agnostic, so
the toy transformer, the zoo's sparse-expert models, its looped dense one, its
delta-rule hybrid and its selective-scan decoder-decoder at tiny sizes run
the SAME in-process master + real worker subprocesses over gRPC that
`tests/test_e2e_local.py` runs MNIST through. A file of its own so that two
xdist workers share the job tests.
"""

from tests.jobs import patient_master, run_job
from tests.test_e2e_local import job_config


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
    # the transformer's step is the longest compile of this file
    master, _, counts = run_job(cfg, tmp_path, master_of=patient_master)
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


def test_local_afmoe_job_end_to_end(tmp_path):
    """Trinity's block (attention gated on its output, q/k head norms, rotary
    positions in the sliding layers only, four norms a layer; published layers
    0, 2, 3 at a period of 2: a dense sliding layer, a sparse sliding one, a
    sparse full one; a held share of sigmoid-routed experts with a centred
    selection bias, a shared expert) through the same master/worker path,
    evaluation — the gates' means among its metrics — included."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.afmoe.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
            "kept_layers": "0,2,3", "num_dense_layers": 2, "global_attn_every_n_layers": 2,
            "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "sliding_window": 8, "num_experts": 4, "router_experts": 16,
            "first_expert": 4, "num_experts_per_tok": 3, "moe_intermediate_size": 24,
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
    assert abs(results["gate_mean_sliding"] - 0.5) < 0.1 and abs(results["gate_mean_full"] - 0.5) < 0.1
    assert master.servicer.mean_training_loss() < 6.0       # ln 256 = 5.5, no auxiliary term


def test_local_ouro_job_end_to_end(tmp_path):
    """Ouro's loop (two layers run three times over shared weights, an exit
    after every pass through one head and one gate, the expected loss over the
    exits with its entropy term; the module's outputs a pytree the zoo's loss
    makes the logits from) through the same master/worker path, evaluation —
    the mean exit distribution among its metrics — included, and a checkpoint
    saved by the worker and restored here."""
    import jax
    import numpy as np

    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.checkpoint import CheckpointManager
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = job_config(
        tmp_path,
        model_def="transformer.ouro.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 48, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
            "intermediate_size": 96, "total_ut_steps": 3, "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=16,
    )
    # a worker reaped while it imports beside five other xdist workers is
    # ROADMAP C21's, not this case's
    master, _, counts = run_job(cfg, tmp_path, master_of=patient_master)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    shares = [results[f"exit_share_{t}"] for t in (1, 2, 3, 4)]
    assert abs(sum(shares) - 1.0) < 1e-4 and shares[3] == 0.0 and min(shares[:3]) > 0.05
    # ln 256 = 5.55; the entropy term takes at most 0.1 ln 3 off it
    assert 5.0 < master.servicer.mean_training_loss() < 6.0

    trainer = Trainer(ModelSpec.from_config(cfg), build_mesh(devices=jax.devices()[:1]))
    example = {"features": np.zeros((4, 32), np.int32), "labels": np.zeros((4, 32), np.int32),
               "mask": np.ones((4,), np.float32)}
    checkpoints = CheckpointManager(str(tmp_path / "ckpt"))
    restored = checkpoints.restore(trainer.abstract_train_state(example))
    checkpoints.close()
    assert int(restored.step) == checkpoints.last_restored_step >= 16
    assert restored.params["wq"].shape == (2, 48, 64)
    assert restored.params["exit_gate_w"].shape == (48,)
    assert int(restored.extra_vars["loop"]["layer_applications"]) == 6 * int(restored.step)


def test_local_kimi_linear_job_end_to_end(tmp_path):
    """Kimi Linear's layers (a delta-rule mixer with a decay a channel in
    layer 1, latent attention without positions in layer 2; a dense
    feed-forward then a held share of sigmoid-routed experts with a selection
    bias and a shared expert) through the same master/worker path, evaluation
    — the mixer's own figures among its metrics — included, and a checkpoint
    saved by the worker and restored here."""
    import jax
    import numpy as np

    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.checkpoint import CheckpointManager
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = job_config(
        tmp_path,
        model_def="transformer.kimi_linear.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 48, "num_hidden_layers": 2,
            "kda_layers": "1", "full_attn_layers": "2", "intermediate_size": 96,
            "linear_num_heads": 4, "linear_head_dim": 16, "num_attention_heads": 4,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "num_experts": 4, "router_experts": 16, "first_expert": 4,
            "num_experts_per_token": 3, "moe_intermediate_size": 24, "kda_chunk": 16,
            "kda_chunks_per_block": 2, "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=16,
    )
    # a worker reaped while it imports beside five other xdist workers is
    # ROADMAP C21's, not this case's
    master, _, counts = run_job(cfg, tmp_path, master_of=patient_master)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert results["kda_log_decay_min"] < 0 < results["kda_state_rms"]
    assert abs(results["kda_beta_mean"] - 0.5) < 0.1
    assert master.servicer.mean_training_loss() < 6.0       # ln 256 = 5.5, no auxiliary term

    trainer = Trainer(ModelSpec.from_config(cfg), build_mesh(devices=jax.devices()[:1]))
    example = {"features": np.zeros((4, 32), np.int32), "labels": np.zeros((4, 32), np.int32),
               "mask": np.ones((4,), np.float32)}
    checkpoints = CheckpointManager(str(tmp_path / "ckpt"))
    restored = checkpoints.restore(trainer.abstract_train_state(example))
    checkpoints.close()
    assert int(restored.step) == checkpoints.last_restored_step >= 16
    assert restored.params["kda_wq"].shape == (1, 48, 64)
    assert restored.params["q_proj"].shape == (1, 48, 96)
    assert restored.extra_vars["router_state"]["e_score_correction_bias"].shape == (1, 16)
    # 1 KDA layer x 4 sequences x 4 heads x 2 chunks of 16, every step
    assert int(restored.extra_vars["kda"]["chunks"]) == 4 * 4 * 2 * int(restored.step)


def test_local_phi4flash_job_end_to_end(tmp_path):
    """Phi-4-mini-flash's six kept layers (Mamba, sliding-window differential
    attention, the Mamba layer whose scan output is the memory, the full layer
    whose keys and values are shared, a GMU, a cross layer; one matrix embedding
    and head) through the same master/worker path, evaluation included, and a
    checkpoint saved by the worker and restored here."""
    import jax
    import numpy as np

    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.checkpoint import CheckpointManager
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = job_config(
        tmp_path,
        model_def="transformer.phi4flash.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 6,
            "kept_layers": "0,1,16,17,18,19", "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 96, "sliding_window": 8,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=16,
    )
    # a worker reaped while it imports beside five other xdist workers is
    # ROADMAP C21's, not this case's
    master, _, counts = run_job(cfg, tmp_path, master_of=patient_master)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert master.servicer.mean_training_loss() < 6.0       # ln 256 = 5.5, no auxiliary term

    trainer = Trainer(ModelSpec.from_config(cfg), build_mesh(devices=jax.devices()[:1]))
    example = {"features": np.zeros((4, 32), np.int32), "labels": np.zeros((4, 32), np.int32),
               "mask": np.ones((4,), np.float32)}
    checkpoints = CheckpointManager(str(tmp_path / "ckpt"))
    restored = checkpoints.restore(trainer.abstract_train_state(example))
    checkpoints.close()
    assert int(restored.step) == checkpoints.last_restored_step >= 16
    assert restored.params["mamba_A_log"].shape == (2, 128, 16)
    assert restored.params["attn_qkv"].shape == (2, 64, 128)
    assert restored.params["cross_q"].shape == (1, 64, 64)
    assert "head" not in restored.params                    # the embedding is the head
    # 2 Mamba layers x 4 sequences x 32 tokens x 128 channels x 16, every step
    assert float(restored.extra_vars["s6"]["scan_elements"]) == 2 * 4 * 32 * 128 * 16 * int(
        restored.step)
    assert int(restored.extra_vars["memory"]["reads"]) == int(restored.step)


def test_local_lfm2_moe_job_end_to_end(tmp_path):
    """LFM2-MoE's three kinds of layer (a dense convolution layer, a sparse
    attention layer, a sparse convolution layer by PUBLISHED index; 2 of 8
    sigmoid-routed experts held; one matrix embedding and head) through the
    same master/worker path, evaluation included, and a checkpoint saved by
    the worker and restored here."""
    import jax
    import numpy as np

    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.checkpoint import CheckpointManager
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = job_config(
        tmp_path,
        model_def="transformer.lfm2_moe.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
            "kept_layers": "0,2,3", "layer_types": "conv,conv,full_attention,conv",
            "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
            "num_experts": 2, "router_experts": 8, "first_expert": 2,
            "num_experts_per_tok": 2, "moe_intermediate_size": 24,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=16,
    )
    master, _, counts = run_job(cfg, tmp_path, master_of=patient_master)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert master.servicer.mean_training_loss() < 6.0       # ln 256 = 5.5, no auxiliary term

    trainer = Trainer(ModelSpec.from_config(cfg), build_mesh(devices=jax.devices()[:1]))
    example = {"features": np.zeros((4, 32), np.int32), "labels": np.zeros((4, 32), np.int32),
               "mask": np.ones((4,), np.float32)}
    checkpoints = CheckpointManager(str(tmp_path / "ckpt"))
    restored = checkpoints.restore(trainer.abstract_train_state(example))
    checkpoints.close()
    assert int(restored.step) == checkpoints.last_restored_step >= 16
    assert restored.params["conv_in"].shape == (2, 64, 192)
    assert restored.params["conv_w"].shape == (2, 3, 64)
    assert restored.params["wq"].shape == (1, 64, 64)
    assert restored.params["w_gate"].shape == (2, 2, 64, 24)
    assert "head" not in restored.params                    # the embedding is the head
    bias = restored.extra_vars["router_state"]["expert_bias"]
    assert bias.shape == (2, 8) and float(np.max(np.abs(bias))) > 0
    # no kernel on the CPU: every convolution took the plain route
    assert int(restored.extra_vars["conv"]["kernel_convs"]) == 0


def test_local_qwen3_next_job_end_to_end(tmp_path):
    """Qwen3-Next's two kinds of layer by PUBLISHED index (a Gated DeltaNet
    layer — the scalar delta rule, 2 key heads read by 4 value heads — and the
    gated attention layer; 4 of 16 softmax-routed experts held beside the gated
    shared expert; the head's loss in row blocks, the auxiliary loss sown)
    through the same master/worker path, evaluation included."""
    cfg = job_config(
        tmp_path,
        model_def="transformer.qwen3_next.custom_model",
        model_params={
            "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
            "kept_layers": "2,3", "linear_key_head_dim": 16, "linear_value_head_dim": 16,
            "linear_num_key_heads": 2, "linear_num_value_heads": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "num_experts": 4, "router_experts": 16, "first_expert": 4,
            "num_experts_per_tok": 3, "moe_intermediate_size": 24,
            "shared_expert_intermediate_size": 24, "gdn_chunk": 16,
            "compute_dtype": "float32",
        },
        training_data="synthetic://lm?n=128&shards=4&vocab=256&seq=32",
        validation_data="synthetic://lm?n=16&shards=1&vocab=256&seq=32",
        records_per_task=32,
        minibatch_size=4,
        steps_per_dispatch=4,
    )
    master, _, counts = run_job(cfg, tmp_path, master_of=patient_master)
    assert counts["finished_training"] == 4      # 128 / 32
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert 0.0 <= results["token_accuracy"] <= 1.0
    assert set(results) >= {"gdn_log_decay_min", "gdn_beta_mean", "gdn_state_rms"}
    assert results["gdn_log_decay_min"] < 0 < results["gdn_beta_mean"] < 1
    assert master.servicer.mean_training_loss() < 6.0       # ln 256 = 5.5, + 0.002 auxiliary
