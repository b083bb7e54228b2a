"""The Qwen3-Next cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand, the HLO-text scope map with the layers' scopes told apart, the readers of
the five per-layer metrics on a made-up run, and the rehearsal's line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import common

flops = common.load_module("flops", "qwen3_next")
reference = common.load_module("reference", "qwen3_next")
driver = common.load_module("drivers", "resident_lm_stateless")
departures = common.load_module("rehearse", "departures_qwen3_next")

CELL = "qwen3-next-80b-a3b.resident-16k"
NEW_METRICS = ("gdn_delta_rule_ms", "gdn_delta_rule_roofline", "gdn_flash_roofline",
               "gdn_held32_gmm_roofline", "lm_mfu_pct")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# the catalog row's `config` (architectures.jsonl, Qwen3-Next-80B-A3B-Instruct)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}


def _config():
    return common.load_json("configs", "qwen3-next-80b-a3b.json")


def _cut():
    return common.model_params(_config())


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_keeps_the_published_key(key):
    config = _config()
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    params = _cut()
    if key in params:       # and the program is built with it
        assert float(params[key]) == float(config[key])


def test_configuration_file_states_the_cut():
    config = _config()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "parameters": 79_674_391_296, "active_parameters_outside_the_embedding": 3_563_764_480}
    params = _cut()
    assert params["kept_layers"] == "0,1,2,3"
    assert (params["router_experts"], params["num_experts"]) == ("512", "32")
    assert 0 <= int(params["first_expert"]) <= 480 and int(params["first_expert"]) % 32 == 0
    for figure in ("625 667 136", "10.01 GB", "9.32 GiB", "77 791 232"):
        assert figure in config["reduced"]["vocab_size"]
    for figure in ("33 718 464", "27 263 488", "3 145 728", "1 648 531 648",
                   "79 674 391 296"):
        assert figure in config["reduced"]["num_hidden_layers"]
    for figure in ("138 582 208", "132 127 232", "547 873 856"):
        assert figure in config["reduced"]["num_experts"]
    # OLMoE's warm-up in tokens over this deployment's tokens a step
    assert params["warmup_steps"] == str(round(10_485_760_000 / (16 * 16384))) == "40000"
    assert "SIXTEEN chips share each layer" in config["deployment"]
    assert set(config["assumed"]) >= {
        "norms", "gated_deltanet", "decay_init", "gated_attention", "router", "aux_loss",
        "multi_token_prediction", "optimizer", "init", "sequence", "held_share"}
    for key in ("norms", "gated_deltanet", "decay_init", "gated_attention", "router",
                "aux_loss", "init"):
        assert "from memory" in config["assumed"][key], key
    assert "LEFT OUT" in config["assumed"]["multi_token_prediction"]
    assert "contiguous" in config["column_order"]
    assert set(config["changed"]) >= {"recomputation", "chunk", "load_counts"}
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-gdn-16k.json")
    want = {"seq_len": 16384, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "trace_dispatches": 2, "zipf_s": 1.0,
            "generator": "zipf-tokens", "driver": "resident_lm_stateless",
            "rehearse": "tiny-lm-gdn"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    vocab = common.load_json("cardinalities", "qwen3next-vocab-slice.json")
    assert (vocab["vocab_size"], vocab["zipf_s"], vocab["fields"]) == (18992, 1.0, [18992])
    assert 8 * 18992 == 151936
    tiny = common.load_json("rehearse", "tiny-lm-gdn.json")["model_params"]
    assert (tiny["num_hidden_layers"], tiny["kept_layers"]) == (3, "2,3,4")
    assert tiny["linear_num_value_heads"] == 2 * tiny["linear_num_key_heads"]


def test_parameter_counts_by_hand():
    c = 2048
    expert = 3 * c * 512
    gdn = c * 12288 + c * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * c
    attn = c * 8192 + 2 * c * 512 + 4096 * c + 2 * 256
    rest = c * 512 + (expert + c) + 2 * c            # router, gated shared expert, two norms
    assert (expert, gdn, attn) == (3_145_728, 33_718_464, 27_263_488)
    assert gdn + rest + 512 * expert == 1_648_531_648
    assert attn + rest + 512 * expert == 1_642_076_672
    published = 36 * 1_648_531_648 + 12 * 1_642_076_672 + 2 * 151936 * c + c
    assert flops.parameter_count(_cut(), published=True) == published == 79_674_391_296
    held = lambda mixer: mixer + rest + 32 * expert
    assert (held(gdn), held(attn)) == (138_582_208, 132_127_232)
    cut = 3 * held(gdn) + held(attn) + 2 * 18992 * c + c
    assert flops.parameter_count(_cut()) == cut == 625_667_136
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * 625_667_136
    assert 0.59 < 16 * 625_667_136 / 2 ** 30 / 15.75 < 0.60             # 9.32 GiB of state
    # what the issue rules out: eight chips a layer (64 held) is 15.3 GiB of state
    assert 15.3 < 16 * flops.parameter_count({**_cut(), "num_experts": "64"}) / 2 ** 30 < 15.4


def test_a_step_s_flops_by_the_model():
    p, t = _cut(), 16384
    shape = flops.shape(p, 1, t)
    total = shape["model_flops_per_sample"]
    assert 26.2e12 < total < 26.3e12 and 34.5e12 < total * 4 / 3 < 35.1e12
    pairs = t * (t + 1) // 2
    assert shape["visible_pairs_per_head"] == pairs
    assert shape["gdn_attention_flops_per_step"] == 6 * 2 * 256 * 16 * pairs
    # the three Gated DeltaNet mixers 40% of the forward FLOPs, the attention
    # layer's mixer 36%
    gdn = 3 * (6 * t * (2048 * 12288 + 2048 * 64 + 4096 * 2048)) \
        + shape["delta_rule_flops_per_step"]
    attn = 6 * t * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) \
        + shape["gdn_attention_flops_per_step"]
    assert 0.40 < gdn / total < 0.42 and 0.35 < attn / total < 0.37
    # 163 840 pairs a layer, 10 240 of them on the 32 held at even routing
    assert flops.expected_held_pairs(p, t) == 10240
    assert shape["held_expert_matmul_flops_per_step"] == 6 * 4 * 10240 * 3 * 2048 * 512
    # the recurrence by the scalar form's arithmetic: q and k at 16 heads
    macs = 16 * 2 * 64 * 128 + 32 * (64 * 3 * 128 + 3 * 128 * 128)
    assert shape["delta_rule_flops_per_step"] == 6 * macs * t * 3
    assert shape["delta_rule_bytes_per_step"] == 4 * t * 3 * (6 * 2048 + 5 * 4096 + 6 * 32)
    assert shape["parameters"] == 625_667_136 and shape["seq_len"] == t


_OP = 'metadata={op_name="jit(f)/'
_Q = "jvp(Qwen3Next)/qwen3_next/checkpoint"
_QT = "transpose(jvp(Qwen3Next))/qwen3_next/checkpoint/rematted_computation"
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", "jvp(Qwen3Next)/qwen3_next/embed/gather"),
        ("fusion.3", "fusion", f"{_Q}/gdn/mul"),
        ("fusion.4", "fusion", f"{_QT}/gdn/proj/dot_general"),
        ("causal_conv1d_fwd.1", "custom-call", f"{_Q}/gdn/conv/pallas_call"),
        ("fusion.5", "fusion", f"{_Q}/gdn/qk_norm/mul"),
        ("fusion.6", "fusion", f"{_Q}/gdn/gates/exp"),
        ("delta_rule_scalar_fwd.1", "custom-call", f"{_Q}/gdn/delta_rule/pallas_call"),
        ("delta_rule_scalar_bwd.1", "custom-call",
         "transpose(jvp(Qwen3Next))/qwen3_next/checkpoint/gdn/delta_rule/delta_rule/pallas_call"),
        ("fusion.7", "fusion", f"{_Q}/gdn/delta_rule/cumsum"),
        ("fusion.8", "fusion", f"{_Q}/gdn/gate_norm/mul"),
        ("fusion.9", "fusion", f"{_Q}/gdn/out/dot_general"),
        ("fusion.10", "fusion", f"{_Q}/attn/proj/dot_general"),
        ("fusion.11", "fusion", f"{_Q}/attn/qk_norm/mul"),
        ("fusion.12", "fusion", f"{_Q}/attn/rope/mul"),
        ("flash_attention_fwd.3", "custom-call", f"{_Q}/attn/flash/pallas_call"),
        ("flash_attention_bwd.1", "custom-call",
         "transpose(jvp(Qwen3Next))/qwen3_next/checkpoint/attn/flash/pallas_call"),
        ("fusion.13", "fusion", f"{_Q}/attn/gate/mul"),
        ("fusion.14", "fusion", f"{_Q}/attn/out/dot_general"),
        ("fusion.15", "fusion", f"{_Q}/moe/router/dot_general"),
        ("fusion.16", "fusion", f"{_Q}/moe/dispatch/gather"),
        ("grouped_matmul.1", "custom-call", f"{_Q}/moe/experts/pallas_call"),
        ("fusion.17", "fusion", f"{_Q}/moe/combine/scatter-add"),
        ("fusion.18", "fusion", f"{_Q}/moe/shared/dot_general"),
        ("fusion.19", "fusion", "jvp(Qwen3Next)/qwen3_next/head_loss/mul"),
        ("fusion.20", "fusion", "jvp(qwen3_next/head_loss)/while/body/checkpoint/dot_general"),
        ("fusion.21", "fusion", "jvp(Qwen3Next)/qwen3_next/concatenate")]]
    + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "qwen3_next/embed", "fusion.3": "qwen3_next/gdn",
    "fusion.4": "qwen3_next/gdn/proj", "causal_conv1d_fwd.1": "qwen3_next/gdn/conv",
    "fusion.5": "qwen3_next/gdn/qk_norm", "fusion.6": "qwen3_next/gdn/gates",
    "delta_rule_scalar_fwd.1": "qwen3_next/gdn/delta_rule",
    "delta_rule_scalar_bwd.1": "qwen3_next/gdn/delta_rule",
    "fusion.7": "qwen3_next/gdn/delta_rule", "fusion.8": "qwen3_next/gdn/gate_norm",
    "fusion.9": "qwen3_next/gdn/out", "fusion.10": "qwen3_next/attn/proj",
    "fusion.11": "qwen3_next/attn/qk_norm", "fusion.12": "qwen3_next/attn/rope",
    "flash_attention_fwd.3": "qwen3_next/attn/flash",
    "flash_attention_bwd.1": "qwen3_next/attn/flash", "fusion.13": "qwen3_next/attn/gate",
    "fusion.14": "qwen3_next/attn/out", "fusion.15": "qwen3_next/moe/router",
    "fusion.16": "qwen3_next/moe/dispatch", "grouped_matmul.1": "qwen3_next/moe/experts",
    "fusion.17": "qwen3_next/moe/combine", "fusion.18": "qwen3_next/moe/shared",
    "fusion.19": "qwen3_next/head_loss", "fusion.20": "qwen3_next/head_loss",
    "fusion.21": "qwen3_next"}
SECONDS = {name: 0.010 for name in SCOPES_OF_HLO}
SECONDS.update({"delta_rule_scalar_fwd.1": 0.050, "delta_rule_scalar_bwd.1": 0.090,
                "fusion.7": 0.004, "flash_attention_fwd.3": 0.060,
                "flash_attention_bwd.1": 0.140, "grouped_matmul.1": 0.050, "copy.4": 0.010})


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_layers_parts_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 1.1, "window_s": 1.12,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "window": {"step_ms": 560.0, "batch": 1, "chips": 1},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"gdn_attention_flops_per_step": 6.6e12,
                      "delta_rule_flops_per_step": 0.773e12,
                      "delta_rule_bytes_per_step": 6.48e9,
                      "held_expert_matmul_flops_per_step": 0.77e12,
                      "model_flops_per_sample": 26.2e12}}


@pytest.mark.parametrize("name,want", [
    ("gdn_delta_rule_ms", 72.0),         # the kernels 25 + 45, the cumulative sums 2
    ("gdn_delta_rule_roofline", 100 * (6.48e9 / 819e9) / 0.072),     # memory-bound by shape
    ("gdn_flash_roofline", 100 * (6.6e12 / 197e12) / 0.100),         # fwd 30 + bwd 70
    ("gdn_held32_gmm_roofline", 100 * (0.77e12 / 197e12) / 0.025),
    ("lm_mfu_pct", 100 * 26.2e12 / 0.55 / 197e12),
    ("step_ms", 560.0),                  # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 1.1 / 1.12))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert abs(run["trace"]["scope_s"]["unattributed"] - 0.010) < 1e-9       # the copy
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
                                       "kimi_linear/kda/delta_rule": 0.3, "optimizer": 0.2}},
                 "shape": {"gdn_attention_flops_per_step": 1.0, "model_flops_per_sample": 1.0,
                           "delta_rule_flops_per_step": 1.0, "delta_rule_bytes_per_step": 1.0},
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
    assert resolved["traffic"]["name"] == "resident-lm-gdn-16k"
    assert CELL in [w["name"] for w in bench["workloads"]]     # (no count: later PRs add)
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        code = f.read().split('"""')[2]
    assert "model_zoo" not in code and "elasticdl_tpu" not in code and "pallas" not in code
    for name in ("hyper", "loss_terms", "loss", "routers_on", "adamw_step", "PASSES",
                 "TOLERANCES", "EXPERT_PAIRS_FLOOR"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) == {
        "loss_rel", "loss_ce_rel", "loss_aux_rel", "router_same_input_agreement_min",
        "router_weight_rel_median", "routing_agreement_min", "mu_rel_l2", "update_rel_l2"}


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
    assert "SCALAR form" in proc.stdout + proc.stderr
