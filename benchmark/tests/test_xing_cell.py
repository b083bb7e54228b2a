"""The Xing4.0 cell's pieces that need no chip: the configuration file against
the catalog's published keys, shape functions against counts made by hand, the
HLO-text scope map with the hyper-connections' four scopes told apart, and the
readers of the ten per-layer metrics on a made-up run."""

import json

import pytest

from benchmark import common

flops = common.load_module("flops", "xing4")
reference = common.load_module("reference", "xing4")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_xing4")

CELL = "xing4.0-29b-a4b.resident-4k"
NEW_METRICS = ("mhc_ms", "mhc_sinkhorn_ms", "mhc_mix_ms", "mhc_mix_roofline",
               "mla_qk192_ms", "mla_qk192_attn_ms", "mla_qk192_attn_roofline",
               "xing_held_moe_ms", "xing_held_gmm_roofline", "head_loss_ms",
               "xing_dense_mlp_ms", "optimizer_ms")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# the catalog row's `config` (architectures.jsonl, Xing4.0-29B-A4B)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 8,
           "vocab_size": 16384, "num_nextn_predict_layers": 0}
# rope_scaling's keys as model_params flattens them
FLATTENED = {"factor": "rope_factor"}


def _config():
    return common.load_json("configs", "xing4.0-29b-a4b.json")


def _cut():
    return common.model_params(_config())


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_keeps_the_published_key(key):
    config = _config()
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    params = _cut()
    if key in params:       # and the program is built with it
        assert float(params[key]) == float(config[key])
    if key == "rope_scaling":
        for name, value in PUBLISHED[key].items():
            if name != "type":
                assert float(params[FLATTENED.get(name, name)]) == value


def test_configuration_file_states_the_cut():
    config = _config()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert {k: config["published"][k] for k in REDUCED} == {k: PUBLISHED[k] for k in REDUCED}
    params = _cut()
    assert (params["router_experts"], params["first_expert"]) == ("64", "0")
    # the streams are stored in the compute dtype: no key of their own
    assert "stream_dtype" not in params and config["compute_dtype"] == "bfloat16"
    assert params["warmup_steps"] == str(10_485_760_000 // (8 * 4096)) == "320000"
    assert "8 chips share each layer" in config["deployment"]
    assert set(config["assumed"]) >= {"entry", "exit", "initialisation", "sinkhorn_order",
                                      "rotary_layout", "selection_bias", "optimizer"}
    for stated in ("THE STREAMS (the residual state, four a token) are STORED bfloat16",
                   "BECAUSE THE CHECK COULD NOT HOLD MORE", "THE COEFFICIENTS are float32",
                   "mhc_sinkhorn_residual_rel", "BELOW_THE_NOISE"):
        assert stated in config["precision"]
    assert "LEFT OUT, not guessed" in config["reduced"]["num_nextn_predict_layers"]
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                                "vocab_size", "num_nextn_predict_layers"]
    assert config["source"].startswith(entry["source"]) and entry["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-mhc-4k.json")
    want = {"seq_len": 4096, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 400,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_model", "rehearse": "tiny-lm-xing"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert common.load_json("cardinalities", "xing-vocab-slice.json")["vocab_size"] == 16384


def test_parameter_counts_by_hand():
    attention = (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
                 + 768 + 512 + 3584)
    streams = 2 * (14336 * 24 + 3 + 24)
    dense = attention + streams + 3584 + 3 * 3584 * 9216
    expert = 3 * 3584 * 1024
    sparse_rest = attention + streams + 3584 + 3584 * 64 + expert
    assert (attention, streams, dense, expert, sparse_rest) == (
        28_414_720, 688_182, 128_196_918, 11_010_048, 40_345_910)
    cut = dense + 4 * (sparse_rest + 8 * expert) + 2 * 16384 * 3584 + 3584
    assert flops.parameter_count(_cut()) == cut == 759_346_190
    uncut = 2 * dense + 38 * (sparse_rest + 64 * expert) + 2 * 131072 * 3584 + 3584
    assert flops.parameter_count(_cut(), published=True) == uncut == 29_505_502_832
    assert flops.active_parameter_count(_cut(), published=True) == 3_932_487_680
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * cut
    assert 0.71 < 16 * cut / 2 ** 30 / 15.75 < 0.73                  # 11.3 GiB of state
    # what the issue rules out: 16 held, a fifth sparse layer
    assert 16 * flops.parameter_count({**_cut(), "n_routed_experts": "16"}) > 17.7e9
    assert 16 * flops.parameter_count({**_cut(), "num_hidden_layers": "6"}) > 14.1e9


def test_a_step_is_11_7_tflop_and_the_streams_move_4_7_gb():
    p, t = _cut(), 4096
    attention = (t * t // 2) * 32 * (192 + 128) * 2 * 3 * 5
    assert flops.attention_flops_per_sample(p, t) == attention == 2_576_980_377_600
    assert flops.expected_held_pairs(p, t) == 2048
    held = 6 * 4 * 2048 * 3 * 3584 * 1024
    assert flops.held_expert_matmul_flops(p, 4 * 2048) == held
    projections = 28_414_720 - 4864
    every_token = (5 * (projections + 2 * 14336 * 24) + 3 * 3584 * 9216
                   + 4 * (3 * 3584 * 1024 + 3584 * 64) + 3584 * 16384)
    total = 6 * every_token * t + held + attention
    assert flops.model_flops_per_sample(p, t) == total
    assert 11.6e12 < total < 11.8e12
    # ten sub-blocks, the state read and written, forward and backward, bfloat16
    assert flops.mhc_bytes(p, 1, t) == 10 * 4 * (t * 4 * 3584 * 2) == 4_697_620_480
    assert flops.mhc_bytes({**p, "compute_dtype": "float32"}, 1, t) == 9_395_240_960
    shape = flops.shape(p, 1, t)
    assert shape["mla_qk192_attention_flops_per_step"] == attention
    assert shape["held_expert_matmul_flops_per_step"] == held
    assert shape["mhc_bytes_per_step"] == 4_697_620_480
    assert shape["parameters"] == 759_346_190 and shape["seq_len"] == t
    # counted pairs take the place of the even share
    assert flops.shape(p, 1, t, 1000.0)["held_expert_matmul_flops_per_step"] \
        == 6 * 1000 * 3 * 3584 * 1024


_OP = 'metadata={op_name="jit(f)/'
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", "jvp(Xing4)/xing4/checkpoint/mhc/coef/dot_general"),
        ("fusion.3", "fusion", "jvp(Xing4)/xing4/checkpoint/mhc/sinkhorn/while/body/div"),
        ("fusion.4", "fusion", "transpose(jvp(Xing4))/xing4/checkpoint/rematted_computation/mhc/sinkhorn/while/body/mul"),
        ("fusion.5", "fusion", "jvp(Xing4)/xing4/checkpoint/mhc/pre/mul"),
        ("fusion.6", "fusion", "transpose(jvp(Xing4))/xing4/checkpoint/mhc/post_res/mul"),
        ("fusion.7", "fusion", "jvp(Xing4)/xing4/checkpoint/mhc/max"),
        ("fusion.8", "fusion", "jvp(Xing4)/xing4/checkpoint/mla/q_lora/dot_general"),
        ("fusion.9", "fusion", "jvp(Xing4)/xing4/checkpoint/mla/rope/mul"),
        ("flash_attention_fwd.3", "custom-call", "jvp(Xing4)/xing4/checkpoint/mla/attn/pallas_call"),
        ("flash_attention_bwd.3", "custom-call", "transpose(jvp(Xing4))/xing4/checkpoint/mla/attn/pallas_call"),
        ("fusion.10", "fusion", "jvp(Xing4)/xing4/checkpoint/mla/out/dot_general"),
        ("fusion.11", "fusion", "jvp(Xing4)/xing4/checkpoint/dense_mlp/dot_general"),
        ("fusion.12", "fusion", "jvp(Xing4)/xing4/checkpoint/moe/router/dot_general"),
        ("fusion.13", "fusion", "jvp(Xing4)/xing4/checkpoint/moe/shared/dot_general"),
        ("grouped_matmul.2", "custom-call", "jvp(Xing4)/xing4/checkpoint/moe/while/body/experts/pallas_call"),
        ("fusion.14", "fusion", "transpose(jvp(Xing4))/xing4/checkpoint/moe/dispatch/gather"),
        ("fusion.15", "fusion", "jvp(Xing4)/xing4/checkpoint/moe/combine/scatter-add"),
        ("fusion.16", "fusion", "transpose(jvp(xing4/head_loss))/mul"),
        ("fusion.17", "fusion", "jvp(Xing4)/xing4/head_loss/dot_general"),
        ("fusion.18", "fusion", "jvp(Xing4)/xing4/embed/gather"),
        ("fusion.19", "fusion", "jvp(Xing4)/xing4/cos")]] + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "xing4/mhc/coef", "fusion.3": "xing4/mhc/sinkhorn",
    "fusion.4": "xing4/mhc/sinkhorn", "fusion.5": "xing4/mhc/pre", "fusion.6": "xing4/mhc/post_res",
    "fusion.7": "xing4/mhc", "fusion.8": "xing4/mla/q_lora", "fusion.9": "xing4/mla/rope",
    "flash_attention_fwd.3": "xing4/mla/attn", "flash_attention_bwd.3": "xing4/mla/attn",
    "fusion.10": "xing4/mla/out", "fusion.11": "xing4/dense_mlp", "fusion.12": "xing4/moe/router",
    "fusion.13": "xing4/moe/shared", "grouped_matmul.2": "xing4/moe/experts",
    "fusion.14": "xing4/moe/dispatch", "fusion.15": "xing4/moe/combine",
    "fusion.16": "xing4/head_loss", "fusion.17": "xing4/head_loss", "fusion.18": "xing4/embed",
    "fusion.19": "xing4"}
SECONDS = {
    "fusion.1": 0.046, "fusion.2": 0.060, "fusion.3": 0.004, "fusion.4": 0.002, "fusion.5": 0.030,
    "fusion.6": 0.070, "fusion.7": 0.002, "fusion.8": 0.020, "fusion.9": 0.022,
    "flash_attention_fwd.3": 0.020, "flash_attention_bwd.3": 0.038, "fusion.10": 0.028,
    "fusion.11": 0.048, "fusion.12": 0.012, "fusion.13": 0.022, "grouped_matmul.2": 0.048,
    "fusion.14": 0.002, "fusion.15": 0.024, "fusion.16": 0.014, "fusion.17": 0.006,
    "fusion.18": 0.008, "fusion.19": 0.001, "copy.4": 0.003}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_hyper_connections_parts_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.53, "window_s": 0.54,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "window": {"step_ms": 270.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"mla_qk192_attention_flops_per_step": 2.577e12,
                      "held_expert_matmul_flops_per_step": 0.541e12,
                      "mhc_bytes_per_step": 4.698e9}}


@pytest.mark.parametrize("name,want", [
    ("mhc_ms", 84.0),             # coef 30 + sinkhorn 3 + pre 15 + post_res 35 + own 1
    ("mhc_sinkhorn_ms", 3.0),
    ("mhc_mix_ms", 50.0),
    ("mhc_mix_roofline", 100 * (4.698e9 / 819e9) / 0.050),
    ("mla_qk192_ms", 64.0),       # q_lora 10 + rope 11 + kernels 10 + 19 + out 14
    ("mla_qk192_attn_ms", 29.0),
    ("mla_qk192_attn_roofline", 100 * (2.577e12 / 197e12) / 0.029),
    ("xing_held_moe_ms", 43.0),   # router 6 + experts 24 + dispatch 1 + combine 12
    ("xing_held_gmm_roofline", 100 * (0.541e12 / 197e12) / 0.024),
    ("head_loss_ms", 10.0),
    ("step_ms", 270.0),           # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 0.53 / 0.54))])
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
                {"trace": {"steps": 2, "scope_s": {"glm4_moe_lite/mla/attn": 1.0,
                                                   "glm4_moe_lite/moe/experts": 1.0,
                                                   "glm4_moe_lite/head_loss": 1.0},
                           "flash_attention_s": 0.5},
                 "shape": {"held_expert_matmul_flops_per_step": 1.0,
                           "mla_attention_flops_per_step": 1.0},
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
        "step_ms", "device_idle_pct"}
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"]["name"] == "resident-lm-mhc-4k"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        code = f.read().split('"""')[2]
    assert "model_zoo" not in code and "elasticdl_tpu" not in code and "pallas" not in code
    for name in ("hyper", "loss_terms", "loss", "routers_on", "bias_update", "adamw_step",
                 "BIAS", "PASSES", "TOLERANCES", "EXPERT_PAIRS_FLOOR"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) >= {"loss_rel", "loss_ce_rel", "bias_entries_off_share",
                                         "mhc_sinkhorn_residual_rel"}


def test_every_departure_the_issue_names_has_a_patch():
    assert len(departures.DEPARTURES) == 7 and len(departures.CONTROLS) == 2
    assert set(departures.BELOW_THE_NOISE) == {"coefficients_in_bfloat16"}
    assert 0 < reference.TOLERANCES["bias_entries_off_share"] < 0.5
