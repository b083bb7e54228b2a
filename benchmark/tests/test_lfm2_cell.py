"""The LFM2-8B-A1B cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand, the HLO-text scope map with the layers' scopes told apart, the readers of
the thirteen per-layer metrics on a made-up run, and the rehearsal's line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import common

flops = common.load_module("flops", "lfm2_moe")
reference = common.load_module("reference", "lfm2_moe")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_lfm2_moe")

CELL = "lfm2-8b-a1b.resident-32k"
NEW_METRICS = ("lfm2_conv_ms", "lfm2_conv_mix_ms", "lfm2_conv_mix_roofline",
               "lfm2_conv_kernel_roofline", "lfm2_attn_ms", "lfm2_flash_ms",
               "lfm2_flash_roofline", "lfm2_moe_ms", "lfm2_held8_gmm_roofline",
               "lfm2_dense_mlp_ms", "head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
LAYER_TYPES = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)]
# the catalog row's `config` (architectures.jsonl, LFM2-8B-A1B)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": LAYER_TYPES, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 16384}


def _config():
    return common.load_json("configs", "lfm2-8b-a1b.json")


def _cut():
    return common.model_params(_config())


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_keeps_the_published_key(key):
    config = _config()
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    params = _cut()
    if key == "layer_types":
        assert params[key] == ",".join(LAYER_TYPES)
    elif key in params:       # and the program is built with it
        assert float(params[key]) == float(config[key])


def test_configuration_file_states_the_cut():
    config = _config()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"] == {
        "num_hidden_layers": 24, "num_experts": 32, "vocab_size": 65536,
        "parameters": 8_339_929_856, "active_parameters_outside_the_embedding": 1_423_422_208}
    params = _cut()
    assert params["kept_layers"] == "0,2,3,4,5"
    assert (params["router_experts"], params["num_experts"]) == ("32", "8")
    assert 0 <= int(params["first_expert"]) <= 24 and int(params["first_expert"]) % 8 == 0
    for figure in ("507 820 160", "8.13 GB", "7.57 GiB", "33 554 432"):
        assert figure in config["reduced"]["vocab_size"]
    for figure in ("16 783 360", "10 485 888", "44 040 192", "11 010 048", "369 174 528",
                   "8 339 929 856", "1 423 422 208"):
        assert figure in config["reduced"]["num_hidden_layers"]
    for figure in ("104 933 376", "98 635 904"):
        assert figure in config["reduced"]["num_experts"]
    # OLMoE's warm-up in tokens over this deployment's tokens a step
    assert params["warmup_steps"] == str(round(10_485_760_000 / (4 * 32768))) == "80000"
    assert "FOUR chips share each layer" in config["deployment"]
    assert "8 of 32 live here" in config["deployment"]
    assert set(config["assumed"]) >= {
        "gate_order", "no_activation", "conv", "qk_norm", "positions", "norms", "router",
        "tie_word_embeddings", "bias_update", "optimizer", "init", "sequence", "held_share"}
    for key in ("gate_order", "no_activation", "conv", "qk_norm", "positions", "norms",
                "router", "bias_update", "optimizer", "init"):
        assert "from memory" in config["assumed"][key], key
    assert set(config["changed"]) >= {"recomputation", "load_counts"}
    for stated in ("every RMSNorm", "the two gates' products", "depthwise convolution",
                   "bfloat16 operands"):
        assert stated in config["precision"]
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-conv-32k.json")
    want = {"seq_len": 32768, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 400,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_model", "rehearse": "tiny-lm-lfm2"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    vocab = common.load_json("cardinalities", "lfm2-vocab-slice.json")
    assert (vocab["vocab_size"], vocab["zipf_s"], vocab["fields"]) == (16384, 1.0, [16384])
    assert 4 * 16384 == 65536
    tiny = common.load_json("rehearse", "tiny-lm-lfm2.json")["model_params"]
    assert (tiny["num_hidden_layers"], tiny["kept_layers"]) == (3, "0,2,3")
    assert tiny["layer_types"].split(",")[2] == "full_attention"


def test_parameter_counts_by_hand():
    c, f, fe, k = 2048, 7168, 1792, 3
    conv = c * 3 * c + k * c + c * c
    attn = 2 * c * c + 2 * c * 512 + 2 * 64
    norms, dense, expert, router = 2 * c, 3 * c * f, 3 * c * fe, c * 32
    assert (conv, attn, dense, expert, router) == (
        16_783_360, 10_485_888, 44_040_192, 11_010_048, 65_536)
    sparse = lambda mixer, held: mixer + norms + router + held * expert
    assert (sparse(conv, 32), sparse(conv, 8), sparse(attn, 8), conv + norms + dense) == (
        369_174_528, 104_933_376, 98_635_904, 60_827_648)
    published = (2 * (conv + norms + dense) + 16 * sparse(conv, 32) + 6 * sparse(attn, 32)
                 + 65536 * c + c)
    whole = {**_cut(), "num_hidden_layers": "24", "kept_layers": "", "num_experts": "32",
             "vocab_size": "65536"}
    assert flops.parameter_count(whole) == published == 8_339_929_856
    assert flops.active_parameter_count(whole) == (
        18 * conv + 6 * attn + 2 * dense + 22 * (router + 4 * expert)) == 1_423_422_208
    cut = (conv + norms + dense) + sparse(attn, 8) + 3 * sparse(conv, 8) + 16384 * c + c
    assert flops.parameter_count(_cut()) == cut == 507_820_160
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * 507_820_160
    assert 0.47 < 16 * 507_820_160 / 2 ** 30 / 15.75 < 0.49              # 7.57 GiB of state
    # what the issue rules out: a two-way share is 14.3 GB of state
    assert 14.2e9 < 16 * flops.parameter_count(
        {**_cut(), "num_experts": "16", "vocab_size": "32768"}) < 14.4e9


def test_a_step_s_forward_is_17_5_tflop_by_the_model():
    p, t = _cut(), 32768
    shape = flops.shape(p, 1, t)
    total = shape["model_flops_per_sample"]
    assert 17.4e12 < total / 3 < 17.5e12 and 69.8e12 < total * 4 / 3 < 70.0e12
    share = lambda flop: flop / total
    pairs = t * (t + 1) // 2
    assert shape["visible_pairs_per_head"] == pairs
    assert shape["attn_flops_per_step"] == 6 * 2 * 64 * 32 * pairs
    assert 0.25 < share(shape["attn_flops_per_step"]) < 0.26        # the scores alone
    assert 0.25 < share(6 * t * 4 * (2048 * 3 * 2048 + 2048 * 2048)) < 0.26   # four mixers
    # 4 x 32 768 pairs on the 8 held at even routing, 4096 an expert
    assert flops.expected_held_pairs(p, t) == 32768
    assert shape["held_expert_matmul_flops_per_step"] == 6 * 4 * 32768 * 3 * 2048 * 1792
    assert 0.16 < share(shape["held_expert_matmul_flops_per_step"]) < 0.17
    assert 0.16 < share(6 * t * 3 * 2048 * 7168) < 0.17               # the dense layer
    assert 0.12 < share(6 * t * 2048 * 16384) < 0.13                  # the head
    # the mixers' elementwise floor: 4 + 7 planes of T x C float32 a layer
    plane = 4 * t * 2048
    assert shape["gated_conv_bytes_per_step"] == 4 * 11 * plane
    assert shape["conv_kernel_bytes_per_step"] == 4 * 5 * plane
    assert shape["parameters"] == 507_820_160 and shape["seq_len"] == t


_OP = 'metadata={op_name="jit(f)/'
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", "jvp(Lfm2Moe)/lfm2/embed/gather"),
        ("fusion.3", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/conv/mul"),
        ("fusion.4", "fusion", "transpose(jvp(Lfm2Moe))/lfm2/checkpoint/rematted_computation/conv/in_proj/dot_general"),
        ("fusion.5", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/conv/gate_in/mul"),
        ("causal_conv1d_fwd.1", "custom-call", "jvp(Lfm2Moe)/lfm2/checkpoint/conv/conv/pallas_call"),
        ("causal_conv1d_bwd.1", "custom-call", "transpose(jvp(Lfm2Moe))/lfm2/checkpoint/conv/conv/pallas_call"),
        ("fusion.6", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/conv/gate_out/mul"),
        ("fusion.7", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/conv/out_proj/dot_general"),
        ("fusion.8", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/attn/qkv/dot_general"),
        ("fusion.9", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/attn/qk_norm/mul"),
        ("fusion.10", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/attn/rope/mul"),
        ("flash_attention_fwd.3", "custom-call", "jvp(Lfm2Moe)/lfm2/checkpoint/attn/attn/pallas_call"),
        ("flash_attention_bwd_dq.1", "custom-call", "transpose(jvp(Lfm2Moe))/lfm2/checkpoint/attn/attn/pallas_call"),
        ("flash_attention_bwd_dkv.1", "custom-call", "transpose(jvp(Lfm2Moe))/lfm2/checkpoint/attn/attn/pallas_call"),
        ("fusion.11", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/attn/out/dot_general"),
        ("fusion.12", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/dense_mlp/dot_general"),
        ("fusion.13", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/moe/router/dot_general"),
        ("fusion.14", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/moe/dispatch/gather"),
        ("grouped_matmul.1", "custom-call", "jvp(Lfm2Moe)/lfm2/checkpoint/moe/experts/pallas_call"),
        ("fusion.15", "fusion", "jvp(Lfm2Moe)/lfm2/checkpoint/moe/combine/scatter-add"),
        ("fusion.16", "fusion", "jvp(Lfm2Moe)/lfm2/head_loss/mul"),
        ("fusion.17", "fusion", "jvp(lfm2/head_loss)/while/body/checkpoint/dot_general"),
        ("fusion.18", "fusion", "jvp(Lfm2Moe)/lfm2/concatenate")]]
    + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "lfm2/embed", "fusion.3": "lfm2/conv",
    "fusion.4": "lfm2/conv/in_proj", "fusion.5": "lfm2/conv/gate_in",
    "causal_conv1d_fwd.1": "lfm2/conv/conv", "causal_conv1d_bwd.1": "lfm2/conv/conv",
    "fusion.6": "lfm2/conv/gate_out", "fusion.7": "lfm2/conv/out_proj",
    "fusion.8": "lfm2/attn/qkv", "fusion.9": "lfm2/attn/qk_norm", "fusion.10": "lfm2/attn/rope",
    "flash_attention_fwd.3": "lfm2/attn/attn", "flash_attention_bwd_dq.1": "lfm2/attn/attn",
    "flash_attention_bwd_dkv.1": "lfm2/attn/attn", "fusion.11": "lfm2/attn/out",
    "fusion.12": "lfm2/dense_mlp", "fusion.13": "lfm2/moe/router",
    "fusion.14": "lfm2/moe/dispatch", "grouped_matmul.1": "lfm2/moe/experts",
    "fusion.15": "lfm2/moe/combine", "fusion.16": "lfm2/head_loss",
    "fusion.17": "lfm2/head_loss", "fusion.18": "lfm2"}
SECONDS = {
    "fusion.1": 0.050, "fusion.2": 0.004, "fusion.3": 0.006, "fusion.4": 0.060,
    "fusion.5": 0.020, "causal_conv1d_fwd.1": 0.016, "causal_conv1d_bwd.1": 0.014,
    "fusion.6": 0.030, "fusion.7": 0.024, "fusion.8": 0.012, "fusion.9": 0.004,
    "fusion.10": 0.006, "flash_attention_fwd.3": 0.100, "flash_attention_bwd_dq.1": 0.160,
    "flash_attention_bwd_dkv.1": 0.240, "fusion.11": 0.010, "fusion.12": 0.200,
    "fusion.13": 0.008, "fusion.14": 0.020, "grouped_matmul.1": 0.180, "fusion.15": 0.030,
    "fusion.16": 0.006, "fusion.17": 0.100, "fusion.18": 0.002, "copy.4": 0.010}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_layers_parts_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 1.312, "window_s": 1.32,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "window": {"step_ms": 660.0, "batch": 1, "chips": 1},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"attn_flops_per_step": 13.19e12, "gated_conv_bytes_per_step": 11.81e9,
                      "conv_kernel_bytes_per_step": 5.37e9,
                      "held_expert_matmul_flops_per_step": 8.66e12,
                      "model_flops_per_sample": 52.4e12}}


@pytest.mark.parametrize("name,want", [
    ("lfm2_conv_ms", 85.0),              # norm 3 + in 30 + gate_in 10 + conv 15 + gate_out 15 + out 12
    ("lfm2_conv_mix_ms", 40.0),          # gate_in 10 + the kernels 8 + 7 + gate_out 15
    ("lfm2_conv_mix_roofline", 100 * (11.81e9 / 819e9) / 0.040),
    ("lfm2_conv_kernel_roofline", 100 * (5.37e9 / 819e9) / 0.015),
    ("lfm2_attn_ms", 266.0),             # qkv 6 + norm 2 + rope 3 + the kernels 250 + out 5
    ("lfm2_flash_ms", 250.0),            # fwd 50 + bwd_dq 80 + bwd_dkv 120
    ("lfm2_flash_roofline", 100 * (13.19e12 / 197e12) / 0.250),
    ("lfm2_moe_ms", 119.0),              # router 4 + dispatch 10 + experts 90 + combine 15
    ("lfm2_held8_gmm_roofline", 100 * (8.66e12 / 197e12) / 0.090),
    ("lfm2_dense_mlp_ms", 100.0),
    ("head_loss_ms", 53.0),         # the norm 3, the blocks 50
    ("optimizer_ms", 25.0),
    ("lm_mfu_pct", 100 * 52.4e12 / 0.656 / 197e12),
    ("step_ms", 660.0),                  # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 1.312 / 1.32))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.010
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6
    if name.endswith(("_roofline", "_mfu_pct")):
        assert want < 100.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell, and this program in another model's."""
    read = common.load_module("layer_metrics", name).read
    for run in ({"trace": None}, {"trace": {"steps": 2, "window_s": 1.0}, "window": {}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0}, "busy_s": 1.0},
                 "shape": {}, "peaks": None, "window": {"batch": 1, "chips": 1}},
                {"trace": {"steps": 2, "busy_s": 1.0, "flash_attention_s": 0.5,
                           "scope_s": {"olmoe/attn": 1.0, "olmoe/head_loss": 1.0,
                                       "optimizer": 0.2}},
                 "shape": {"attn_flops_per_step": 1.0, "model_flops_per_sample": 1.0},
                 "window": {"batch": 1, "chips": 1},
                 "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}):
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
    share = name.endswith(("_roofline", "_mfu_pct"))
    assert entry["unit"] == ("%" if share else "ms/step")
    assert entry["better"] == ("higher" if share else "lower")
    with open(os.path.join(common.BENCH_DIR, "layer_metrics", name + ".py")) as f:
        assert f.read().startswith(f'"""layer: {entry["layer"]}.')
    resolved = common.resolve_cell(CELL)
    # (a superset: a later PR's unlisted metric reads this cell too)
    assert {m["name"] for m in resolved["per_layer"]} >= set(NEW_METRICS) | {
        "step_ms", "device_idle_pct", "setup_state_s", "setup_compile_s",
        "setup_cache_misses"}
    assert {m["name"] for m in resolved["end_to_end"]} == {"samples_per_s_per_chip", "setup_s"}
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"]["name"] == "resident-lm-conv-32k"
    assert CELL in [w["name"] for w in bench["workloads"]]     # (no count: later PRs add)
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        code = f.read().split('"""')[2]
    assert "model_zoo" not in code and "elasticdl_tpu" not in code and "pallas" not in code
    for name in ("hyper", "loss_terms", "loss", "routers_on", "bias_update", "adamw_step",
                 "BIAS", "PASSES", "TOLERANCES", "EXPERT_PAIRS_FLOOR"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) == {
        "loss_rel", "loss_ce_rel", "router_same_input_agreement_min",
        "router_weight_rel_median", "routing_agreement_min", "mu_rel_l2", "update_rel_l2",
        "bias_entries_off_share"}


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) == {
        "gate_g_left_out", "blocks_permuted", "tap_dropped", "head_norms_left_out",
        "bias_used_as_a_weight", "one_held_expert_left_out"}
    assert set(departures.CONTROLS) == {"conv_planes_in_bfloat16"}
    assert set(departures.BELOW_THE_NOISE_ON_THE_CHIP) == {"conv_planes_in_bfloat16"}
    assert set(departures.REFERENCE_CONTROLS) == {"reference_in_bfloat16"}
    assert set(departures.REPORTED) == {"renormaliser_1e-20"}


def test_the_rehearsal_prints_a_correct_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(common.ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "2147484000", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=600, cwd=common.ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    assert "selection bias settled for 6 forward passes" in proc.stdout
