"""The Kimi Linear cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand, the HLO-text scope map with the mixer's seven scopes told apart, the
readers of the twelve per-layer metrics on a made-up run, and the rehearsal's
line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import common

flops = common.load_module("flops", "kimi_linear")
reference = common.load_module("reference", "kimi_linear")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_kimi_linear")

CELL = "kimi-linear-48b-a3b.resident-16k"
NEW_METRICS = ("kda_ms", "kda_delta_rule_ms", "kda_delta_rule_roofline", "kda_conv_gates_ms",
               "kda_proj_ms", "kda_mla_ms", "kda_mla_attn_ms", "kda_mla_attn_roofline",
               "kda_held_moe_ms", "kda_held_gmm_roofline", "head_loss_ms",
               "optimizer_ms")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
KDA_LAYERS = [l for l in range(1, 28) if l not in (4, 8, 12, 16, 20, 24, 27)]
# the catalog row's `config` (architectures.jsonl, Kimi-Linear-48B-A3B-Instruct)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                           "kda_layers": KDA_LAYERS, "num_heads": 32,
                           "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
# linear_attn_config's keys as model_params flattens them
FLATTENED = {"num_heads": "linear_num_heads", "head_dim": "linear_head_dim",
             "short_conv_kernel_size": "short_conv_kernel_size"}


def _config():
    return common.load_json("configs", "kimi-linear-48b-a3b.json")


def _cut():
    return common.model_params(_config())


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_keeps_the_published_key(key):
    config = _config()
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    params = _cut()
    if key == "linear_attn_config":
        for name, flat in FLATTENED.items():
            assert int(params[flat]) == PUBLISHED[key][name]
        for name in ("kda_layers", "full_attn_layers"):
            assert [int(l) for l in params[name].split(",")] == PUBLISHED[key][name]
    elif key in params and key != "head_dim":     # and the program is built with it
        assert float(params[key]) == float(config[key])


def test_configuration_file_states_the_cut():
    config = _config()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert {k: config["published"][k] for k in REDUCED} == {k: PUBLISHED[k] for k in REDUCED}
    assert config["published"]["parameters"] == 49_122_675_072
    assert config["published"]["active_parameters"] == 3_484_453_248
    params = _cut()
    assert (params["router_experts"], params["first_expert"]) == ("256", "0")
    assert "q_lora_rank" not in params and "rope_theta" not in params
    assert params["warmup_steps"] == str(10_485_760_000 // (32 * 16384)) == "20000"
    assert config["compute_dtype"] == "bfloat16"
    assert "32 chips share each layer" in config["deployment"]
    assert set(config["assumed"]) >= {
        "conv", "qk_norm", "low_rank_gates", "decay", "beta", "output_gate",
        "latent_attention", "router", "bias_update", "optimizer", "init", "sequence",
        "held_share"}
    for stated in ("the triangular inverse", "the state S and what is added to it",
                   "operands rounded AFTER the decay has been applied in float32",
                   "16 bytes a parameter"):
        assert stated in config["precision"]
    assert set(config["changed"]) >= {"recomputation", "chunk"}
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    # the catalog's `source_url` to the letter; the report is named beside it
    assert entry["source"] == config["source"] and "arXiv:2510.26692" in entry["why"]
    assert "arXiv:2510.26692" in config["equations"]
    assert config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-kda-16k.json")
    want = {"seq_len": 16384, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 400,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_model", "rehearse": "tiny-lm-kda"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert common.load_json("cardinalities", "kimi-vocab-slice.json")["vocab_size"] == 20480
    tiny = common.load_json("rehearse", "tiny-lm-kda.json")["model_params"]
    assert (tiny["num_hidden_layers"], tiny["linear_num_heads"], tiny["linear_head_dim"],
            tiny["router_experts"], tiny["num_experts"]) == (5, 4, 16, 16, 4)


def test_parameter_counts_by_hand():
    kda = (3 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 32
           + 4096 + 128 + 4096 * 2304)
    latent = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    expert, dense_mlp, router, norms = 3 * 2304 * 1024, 3 * 2304 * 9216, 2304 * 256, 2 * 2304
    assert (kda, latent, expert, dense_mlp, router) == (
        39_514_272, 29_114_880, 7_077_888, 63_700_992, 589_824)
    layer_1 = kda + dense_mlp + norms
    sparse_kda, sparse_latent = (kda + norms + router + expert,
                                 latent + norms + router + expert)
    assert (layer_1, sparse_kda, sparse_latent) == (103_219_872, 47_186_592, 36_787_200)
    cut = (layer_1 + 3 * (sparse_kda + 8 * expert) + (sparse_latent + 8 * expert)
           + 2 * 20480 * 2304 + 2304)
    assert flops.parameter_count(_cut()) == cut == 602_433_408
    uncut = (layer_1 + 19 * (sparse_kda + 256 * expert) + 7 * (sparse_latent + 256 * expert)
             + 2 * 163840 * 2304 + 2304)
    assert flops.parameter_count(_cut(), published=True) == uncut == 49_122_675_072
    assert flops.active_parameter_count(_cut(), published=True) == 3_484_453_248
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * cut
    assert 0.56 < 16 * cut / 2 ** 30 / 15.75 < 0.58                  # 8.98 GiB of state
    # what the issue rules out: 16 held, a sixth layer
    assert 13.2e9 < 16 * flops.parameter_count({**_cut(), "num_experts": "16"}) < 13.3e9
    assert 11.2e9 < 16 * flops.parameter_count({**_cut(), "num_hidden_layers": "6"}) < 11.4e9


def test_a_step_is_42_4_tflop_and_the_recurrence_moves_16_gb():
    p, t = _cut(), 16384
    attention = (t * t // 2) * 32 * (192 + 128) * 2 * 3
    assert flops.attention_flops_per_sample(p, t) == attention
    assert flops.expected_held_pairs(p, t) == 4096
    held = 6 * 4 * 4096 * 3 * 2304 * 1024
    assert flops.held_expert_matmul_flops(p, 4 * 4096) == held
    recurrence = 3 * 2 * (3 * 64 * 128 + 2 * 64 * 128 + 3 * 128 * 128) * 32 * t * 4
    assert flops.delta_rule_flops_per_sample(p, t) == recurrence
    assert flops.delta_rule_bytes_per_sample(p, t) == 15 * t * 4096 * 4 * 4 == 16_106_127_360
    kda_matmul = 39_514_272 - (3 * 4096 * 4 + 32 + 4096 + 128)
    every_token = (4 * kda_matmul + (29_114_880 - 512) + 3 * 2304 * 9216
                   + 4 * (3 * 2304 * 1024 + 2304 * 256) + 2304 * 20480)
    total = 6 * every_token * t + held + attention + recurrence
    assert flops.model_flops_per_sample(p, t) == total
    assert 42.3e12 < total < 42.5e12
    assert round(total / 3 / t / 1e6) == 862                           # MFLOP a token, forward
    shape = flops.shape(p, 1, t)
    assert shape["kda_mla_attention_flops_per_step"] == attention
    assert shape["held_expert_matmul_flops_per_step"] == held
    assert shape["delta_rule_flops_per_step"] == recurrence
    assert shape["delta_rule_bytes_per_step"] == 16_106_127_360
    assert shape["parameters"] == 602_433_408 and shape["seq_len"] == t
    # the count's chunk is a constant of the COUNT: no key of the program moves it
    assert flops.shape({**p, "kda_chunk": "128"}, 1, t)["delta_rule_flops_per_step"] == recurrence
    # counted pairs take the place of the even share
    assert flops.shape(p, 1, t, 1000.0)["held_expert_matmul_flops_per_step"] \
        == 6 * 1000 * 3 * 2304 * 1024


_OP = 'metadata={op_name="jit(f)/'
_K = "jvp(KimiLinear)/kimi_linear/checkpoint/"
_KT = "transpose(jvp(KimiLinear))/kimi_linear/checkpoint/"
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", _K + "kda/proj/dot_general"),
        ("fusion.3", "fusion", _K + "kda/conv/mul"),
        ("fusion.4", "fusion", _KT + "rematted_computation/kda/gates/softplus"),
        ("fusion.5", "fusion", _K + "kda/qk_norm/rsqrt"),
        ("fusion.6", "fusion", _K + "kda/delta_rule/while/body/closed_call/while/body/dot_general"),
        ("fusion.7", "fusion", _KT + "kda/delta_rule/while/body/transpose(jvp())/dot_general"),
        ("fusion.8", "fusion", _K + "kda/out_gate/logistic"),
        ("fusion.9", "fusion", _KT + "kda/out/dot_general"),
        ("fusion.10", "fusion", _K + "kda/counters/reduce_min"),
        ("fusion.11", "fusion", _K + "kda/mul"),
        ("fusion.12", "fusion", _K + "mla/q_proj/dot_general"),
        ("fusion.13", "fusion", _K + "mla/kv_lora/dot_general"),
        ("fusion.14", "fusion", _K + "mla/rope/concatenate"),
        ("flash_attention_fwd.3", "custom-call", _K + "mla/attn/pallas_call"),
        ("flash_attention_bwd.3", "custom-call", _KT + "mla/attn/pallas_call"),
        ("fusion.15", "fusion", _K + "mla/out/dot_general"),
        ("fusion.16", "fusion", _K + "dense_mlp/dot_general"),
        ("fusion.17", "fusion", _K + "moe/router/dot_general"),
        ("fusion.18", "fusion", _K + "moe/shared/dot_general"),
        ("grouped_matmul.2", "custom-call", _K + "moe/experts/pallas_call"),
        ("fusion.19", "fusion", _KT + "moe/dispatch/gather"),
        ("fusion.20", "fusion", _K + "moe/combine/scatter-add"),
        ("fusion.21", "fusion", "transpose(jvp(kimi_linear/head_loss))/mul"),
        ("fusion.22", "fusion", "jvp(KimiLinear)/kimi_linear/head_loss/dot_general"),
        ("fusion.23", "fusion", "jvp(KimiLinear)/kimi_linear/embed/gather")]]
    + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "kimi_linear/kda/proj", "fusion.3": "kimi_linear/kda/conv",
    "fusion.4": "kimi_linear/kda/gates", "fusion.5": "kimi_linear/kda/qk_norm",
    "fusion.6": "kimi_linear/kda/delta_rule", "fusion.7": "kimi_linear/kda/delta_rule",
    "fusion.8": "kimi_linear/kda/out_gate", "fusion.9": "kimi_linear/kda/out",
    "fusion.10": "kimi_linear/kda/counters", "fusion.11": "kimi_linear/kda",
    "fusion.12": "kimi_linear/mla/q_proj", "fusion.13": "kimi_linear/mla/kv_lora",
    "fusion.14": "kimi_linear/mla/rope", "flash_attention_fwd.3": "kimi_linear/mla/attn",
    "flash_attention_bwd.3": "kimi_linear/mla/attn", "fusion.15": "kimi_linear/mla/out",
    "fusion.16": "kimi_linear/dense_mlp", "fusion.17": "kimi_linear/moe/router",
    "fusion.18": "kimi_linear/moe/shared", "grouped_matmul.2": "kimi_linear/moe/experts",
    "fusion.19": "kimi_linear/moe/dispatch", "fusion.20": "kimi_linear/moe/combine",
    "fusion.21": "kimi_linear/head_loss", "fusion.22": "kimi_linear/head_loss",
    "fusion.23": "kimi_linear/embed"}
SECONDS = {
    "fusion.1": 0.046, "fusion.2": 0.060, "fusion.3": 0.030, "fusion.4": 0.020, "fusion.5": 0.010,
    "fusion.6": 0.080, "fusion.7": 0.120, "fusion.8": 0.016, "fusion.9": 0.020,
    "fusion.10": 0.002, "fusion.11": 0.004, "fusion.12": 0.020, "fusion.13": 0.012,
    "fusion.14": 0.004, "flash_attention_fwd.3": 0.040, "flash_attention_bwd.3": 0.100,
    "fusion.15": 0.014, "fusion.16": 0.048, "fusion.17": 0.012, "fusion.18": 0.022,
    "grouped_matmul.2": 0.024, "fusion.19": 0.002, "fusion.20": 0.012, "fusion.21": 0.014,
    "fusion.22": 0.006, "fusion.23": 0.008, "copy.4": 0.003}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_mixer_s_parts_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.74, "window_s": 0.75,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "window": {"step_ms": 375.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"kda_mla_attention_flops_per_step": 8.246e12,
                      "held_expert_matmul_flops_per_step": 0.696e12,
                      "delta_rule_flops_per_step": 1.134e12,
                      "delta_rule_bytes_per_step": 16.106e9}}


@pytest.mark.parametrize("name,want", [
    ("kda_ms", 181.0),            # every kda scope: 362 ms over two steps
    ("kda_delta_rule_ms", 100.0),
    # bound by bytes: 16.1 GB / 819 GB/s = 19.7 ms against 5.8 ms of FLOPs
    ("kda_delta_rule_roofline", 100 * (16.106e9 / 819e9) / 0.100),
    ("kda_conv_gates_ms", 38.0),  # conv 15 + gates 10 + qk_norm 5 + out_gate 8
    ("kda_proj_ms", 40.0),
    ("kda_mla_ms", 95.0),         # q_proj 10 + kv_lora 6 + rope 2 + kernels 20 + 50 + out 7
    ("kda_mla_attn_ms", 70.0),
    ("kda_mla_attn_roofline", 100 * (8.246e12 / 197e12) / 0.070),
    ("kda_held_moe_ms", 25.0),    # router 6 + experts 12 + dispatch 1 + combine 6
    ("kda_held_gmm_roofline", 100 * (0.696e12 / 197e12) / 0.012),
    ("head_loss_ms", 10.0),
    ("optimizer_ms", 23.0),
    ("step_ms", 375.0),           # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 0.74 / 0.75))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.003
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell, and this program in another model's."""
    read = common.load_module("layer_metrics", name).read
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None},
                {"trace": {"steps": 2, "scope_s": {"xing4/mla/attn": 1.0,
                                                   "xing4/moe/experts": 1.0,
                                                   "xing4/head_loss": 1.0, "optimizer": 1.0},
                           "flash_attention_s": 0.5},
                 "shape": {"held_expert_matmul_flops_per_step": 1.0,
                           "mla_qk192_attention_flops_per_step": 1.0},
                 "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}):
        if name in FOLDED and set((run["trace"] or {}).get("scope_s", ())) - {"unattributed"}:
            continue
        assert read(run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_per_layer_entry_is_bound_to_the_cell(name):
    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"] if name in FOLDED else entry["workloads"] == [CELL]
    assert entry["moves"] == "samples_per_s_per_chip" and entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if name.endswith("_roofline") else "ms/step")
    assert entry["better"] == ("higher" if name.endswith("_roofline") else "lower")
    resolved = common.resolve_cell(CELL)
    assert {m["name"] for m in resolved["per_layer"]} == set(NEW_METRICS) | {
        "step_ms", "device_idle_pct", "setup_state_s", "setup_compile_s", "setup_cache_misses"}
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"]["name"] == "resident-lm-kda-16k"
    assert len(bench["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program_and_has_no_chunk_algebra():
    with open(reference.__file__) as f:
        code = f.read().split('"""')[2]
    assert "model_zoo" not in code and "elasticdl_tpu" not in code and "pallas" not in code
    for chunked in ("cumsum", "linalg", "tril", "solve", "inv("):
        assert chunked not in code, chunked
    for name in ("hyper", "loss_terms", "loss", "routers_on", "bias_update", "adamw_step",
                 "BIAS", "PASSES", "TOLERANCES", "EXPERT_PAIRS_FLOOR"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) >= {"loss_rel", "loss_ce_rel", "bias_entries_off_share"}


def test_every_departure_the_issue_names_has_a_patch():
    assert len(departures.DEPARTURES) == 8
    assert set(departures.CONTROLS) == {"cumulative_decay_in_bfloat16"}
    assert set(departures.BELOW_THE_NOISE) == {"state_in_bfloat16"}
    assert departures.MUST_FAIL == ("scalar_decay_a_head", "erase_term_left_out")


def test_the_rehearsal_prints_a_correct_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--workload", CELL,
         "--rehearse", "--seconds", "2", "--seed", "2147483659"],
        capture_output=True, text=True, env=env, cwd=common.ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    assert line["device"]["platform"] == "cpu"
