"""The Trinity-Mini cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand, the HLO-text scope map with the two kinds of attention told apart, and
the readers of the nine per-layer metrics on a made-up run."""

import json

import pytest

from benchmark import common

flops = common.load_module("flops", "afmoe")
reference = common.load_module("reference", "afmoe")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_afmoe")

CELL = "trinity-mini.resident-16k"
NEW_METRICS = ("gated_swa_ms", "gated_swa_attn_ms", "gated_swa_attn_roofline",
               "nope_attn_ms", "nope_attn_roofline", "attn_gate_ms",
               "sigmoid_held16_moe_ms", "sigmoid_held16_gmm_roofline", "head_loss_ms")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# the catalog row's `config` (architectures.jsonl, Trinity-Mini) but layer_types
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 25024}


def _config():
    return common.load_json("configs", "trinity-mini.json")


def _cut():
    return common.model_params(_config())


def _uncut():
    return dict(_cut(), vocab_size="200192", num_hidden_layers="32", kept_layers="",
                num_experts="128", router_experts="0")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_keeps_the_published_key(key):
    config = _config()
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    params = _cut()
    if key in params:       # and the program is built with it
        assert float(params[key]) == float(config[key])


def test_configuration_file_states_the_cut():
    config = _config()
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"]["parameters"] == 26_123_970_560
    assert {k: config["published"][k] for k in REDUCED} == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 200192}
    params = _cut()
    assert (params["router_experts"], params["first_expert"], params["kept_layers"]) == (
        "128", "0", "0,2,3,4,5")
    assert params["warmup_steps"] == str(10_485_760_000 // (8 * 16384)) == "80000"
    assert "8 chips share each layer" in config["deployment"]
    assert set(config["assumed"]) >= {"gate", "qk_norm", "positions", "sliding_mask",
                                      "bias_update", "init", "held_share"}
    assert "memory_analysis" in config["changed"]["recomputation"]
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-bias-16k.json")
    want = {"seq_len": 16384, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "trace_dispatches": 2,
            "zipf_s": 1.0, "generator": "zipf-tokens", "driver": "resident_lm_model",
            "rehearse": "tiny-lm-afmoe"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["settle_router_steps"] % 100 == 0 and traffic["settle_router_steps"] >= 400
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert common.load_json("cardinalities", "trinity-vocab-slice.json")["vocab_size"] == 25024


def test_parameter_counts_by_hand():
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 4096 + 2 * 128
    dense = attention + 4 * 2048 + 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    sparse_rest = attention + 4 * 2048 + 2048 * 128 + expert
    assert (attention, dense, expert, sparse_rest) == (
        27_263_232, 65_020_160, 6_291_456, 33_825_024)
    cut = dense + 4 * (sparse_rest + 16 * expert) + 2 * 25024 * 2048 + 2048
    assert flops.parameter_count(_cut()) == cut == 705_473_792
    uncut = 2 * dense + 30 * (sparse_rest + 128 * expert) + 2 * 200192 * 2048 + 2048
    assert flops.parameter_count(_uncut()) == uncut == 26_123_970_560       # the card's 26B
    assert flops.active_parameter_count(_uncut()) == 3_064_463_360          # its A3B
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * cut
    assert 0.66 < 16 * cut / 2 ** 30 / 15.75 < 0.68                          # 10.51 GiB of state


def test_a_step_is_40_tflop_and_the_attention_blocks_65_percent_of_it():
    p, t = _cut(), 16384
    sliding, full = 2048 * 2049 // 2 + (t - 2048) * 2048, t * (t + 1) // 2
    assert (sliding, full) == (31_458_304, 134_225_920)
    assert flops.visible_pairs(t, 2048) == sliding and flops.visible_pairs(t) == full
    attn = flops.attention_flops(p, t)
    assert attn == {"sliding": 6.0 * 2 * 128 * 32 * 4 * sliding, "full": 6.0 * 2 * 128 * 32 * full}
    assert 4.2 < full / sliding < 4.3          # one full layer's scores cost 4.27 sliding ones'
    assert flops.expected_held_pairs(p, t) == 16384
    held = 6 * 4 * 16384 * 3 * 2048 * 1024
    assert flops.held_expert_matmul_flops(p, 4 * 16384) == held
    projections = 27_263_232 - 256
    every_token = (5 * projections + 3 * 2048 * 6144
                   + 4 * (3 * 2048 * 1024 + 2048 * 128) + 2048 * 25024)
    total = 6 * every_token * t + held + attn["sliding"] + attn["full"]
    assert flops.model_flops_per_sample(p, t) == total
    assert 39.9e12 < total < 40.1e12
    blocks = 6 * 5 * projections * t + attn["sliding"] + attn["full"]
    assert 0.65 < blocks / total < 0.66
    shape = flops.shape(p, 1, t)
    assert shape["gated_swa_attention_flops_per_step"] == attn["sliding"]
    assert shape["nope_attention_flops_per_step"] == attn["full"]
    assert shape["held_expert_matmul_flops_per_step"] == held
    assert shape["parameters"] == 705_473_792 and shape["seq_len"] == t
    # counted pairs take the place of the even share
    assert flops.shape(p, 1, t, 1000.0)["held_expert_matmul_flops_per_step"] \
        == 6 * 1000 * 3 * 2048 * 1024


_OP = 'metadata={op_name="jit(f)/'
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", "jvp(Afmoe)/afmoe/checkpoint/sliding/qkv/dot_general"),
        ("fusion.3", "fusion", "transpose(jvp(Afmoe))/afmoe/checkpoint/rematted_computation/sliding/rope/mul"),
        ("fusion.4", "fusion", "jvp(Afmoe)/afmoe/checkpoint/sliding/qk_norm/mul"),
        ("fusion.5", "fusion", "jvp(Afmoe)/afmoe/checkpoint/full/qk_norm/mul"),
        ("flash_attention_swa_fwd.3", "custom-call", "jvp(Afmoe)/afmoe/checkpoint/sliding/attn/pallas_call"),
        ("flash_attention_swa_bwd.3", "custom-call", "transpose(jvp(Afmoe))/afmoe/checkpoint/sliding/attn/pallas_call"),
        ("flash_attention_fwd.7", "custom-call", "jvp(Afmoe)/afmoe/checkpoint/full/attn/pallas_call"),
        ("flash_attention_bwd.7", "custom-call", "transpose(jvp(Afmoe))/afmoe/checkpoint/full/attn/pallas_call"),
        ("fusion.6", "fusion", "jvp(Afmoe)/afmoe/checkpoint/sliding/gate/dot_general"),
        ("fusion.7", "fusion", "transpose(jvp(Afmoe))/afmoe/checkpoint/full/gate/mul"),
        ("fusion.8", "fusion", "jvp(Afmoe)/afmoe/checkpoint/full/out/dot_general"),
        ("fusion.9", "fusion", "jvp(Afmoe)/afmoe/checkpoint/sliding/mul"),
        ("fusion.10", "fusion", "jvp(Afmoe)/afmoe/checkpoint/dense_mlp/dot_general"),
        ("fusion.11", "fusion", "jvp(Afmoe)/afmoe/checkpoint/moe/router/dot_general"),
        ("fusion.12", "fusion", "jvp(Afmoe)/afmoe/checkpoint/moe/shared/dot_general"),
        ("grouped_matmul.2", "custom-call", "jvp(Afmoe)/afmoe/checkpoint/moe/while/body/experts/pallas_call"),
        ("fusion.13", "fusion", "transpose(jvp(Afmoe))/afmoe/checkpoint/moe/dispatch/gather"),
        ("fusion.14", "fusion", "jvp(Afmoe)/afmoe/checkpoint/moe/mul"),
        ("fusion.15", "fusion", "transpose(jvp(afmoe/head_loss))/mul"),
        ("fusion.16", "fusion", "jvp(Afmoe)/afmoe/head_loss/dot_general"),
        ("fusion.17", "fusion", "jvp(Afmoe)/afmoe/embed/gather")]] + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "afmoe/sliding/qkv", "fusion.3": "afmoe/sliding/rope",
    "fusion.4": "afmoe/sliding/qk_norm", "fusion.5": "afmoe/full/qk_norm",
    "flash_attention_swa_fwd.3": "afmoe/sliding/attn", "flash_attention_swa_bwd.3": "afmoe/sliding/attn",
    "flash_attention_fwd.7": "afmoe/full/attn", "flash_attention_bwd.7": "afmoe/full/attn",
    "fusion.6": "afmoe/sliding/gate", "fusion.7": "afmoe/full/gate", "fusion.8": "afmoe/full/out",
    "fusion.9": "afmoe/sliding", "fusion.10": "afmoe/dense_mlp", "fusion.11": "afmoe/moe/router",
    "fusion.12": "afmoe/moe/shared", "grouped_matmul.2": "afmoe/moe/experts",
    "fusion.13": "afmoe/moe/dispatch", "fusion.14": "afmoe/moe", "fusion.15": "afmoe/head_loss",
    "fusion.16": "afmoe/head_loss", "fusion.17": "afmoe/embed"}
SECONDS = {
    "fusion.1": 0.050, "fusion.2": 0.040, "fusion.3": 0.020, "fusion.4": 0.010, "fusion.5": 0.002,
    "flash_attention_swa_fwd.3": 0.016, "flash_attention_swa_bwd.3": 0.024,
    "flash_attention_fwd.7": 0.030, "flash_attention_bwd.7": 0.050, "fusion.6": 0.006,
    "fusion.7": 0.004, "fusion.8": 0.008, "fusion.9": 0.002, "fusion.10": 0.070,
    "fusion.11": 0.004, "fusion.12": 0.012, "grouped_matmul.2": 0.030, "fusion.13": 0.006,
    "fusion.14": 0.002, "fusion.15": 0.034, "fusion.16": 0.012, "fusion.17": 0.001,
    "copy.4": 0.002}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_two_kinds_of_attention_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]
    assert not any(scope.startswith("afmoe/full/rope") for scope in flops.SCOPES)


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.4, "window_s": 0.41,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "window": {"step_ms": 218.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"gated_swa_attention_flops_per_step": 6.18e12,
                      "nope_attention_flops_per_step": 6.60e12,
                      "held_expert_matmul_flops_per_step": 2.47e12}}


@pytest.mark.parametrize("name,want", [
    ("gated_swa_ms", 59.0),       # qkv 20 + rope 10 + qk_norm 5 + kernels 8 + 12 + gate 3 + own 1
    ("gated_swa_attn_ms", 20.0),  # the banded kernels, by their scope
    ("gated_swa_attn_roofline", 100 * (6.18e12 / 197e12) / 0.020),
    ("nope_attn_ms", 40.0),
    ("nope_attn_roofline", 100 * (6.60e12 / 197e12) / 0.040),
    ("attn_gate_ms", 5.0),        # both kinds' gate scopes
    ("sigmoid_held16_moe_ms", 27.0),   # router 2 + shared 6 + experts 15 + dispatch 3 + own 1
    ("sigmoid_held16_gmm_roofline", 100 * (2.47e12 / 197e12) / 0.015),
    ("head_loss_ms", 23.0),
    ("step_ms", 218.0),           # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 0.4 / 0.41))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.002
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell, and this program in another model's."""
    read = common.load_module("layer_metrics", name).read
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None},
                {"trace": {"steps": 2, "scope_s": {"mellum/sliding/attn": 1.0,
                                                   "glm4_moe_lite/moe/experts": 1.0},
                           "flash_attention_s": 0.5},
                 "shape": {"held_expert_matmul_flops_per_step": 1.0,
                           "swa_attention_flops_per_step": 1.0},
                 "peaks": {"bf16_flops_per_s": 1.0}}):
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
    resolved = common.resolve_cell(CELL)
    assert {m["name"] for m in resolved["per_layer"]} == set(NEW_METRICS) | {
        "step_ms", "device_idle_pct"}
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"]["name"] == "resident-lm-bias-16k"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and len(bench["workloads"]) == 10
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        text = f.read()
    assert "model_zoo" not in text.split('"""')[2] and "elasticdl_tpu" not in text.split('"""')[2]
    for name in ("hyper", "loss_terms", "loss", "routers_on", "bias_update", "adamw_step",
                 "BIAS", "PASSES", "TOLERANCES", "EXPERT_PAIRS_FLOOR"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) >= {"loss_rel", "loss_ce_rel", "bias_entries_off_share"}


def test_every_departure_the_issue_names_has_a_patch():
    assert len(departures.DEPARTURES) == 18 and len(departures.CONTROLS) == 3
    assert {"bias_update_left_out", "bias_update_mis_signed"} <= set(departures.DEPARTURES)
    # a limit the figure can reach: a share of entries of 1.0 would hold nothing
    assert 0 < reference.TOLERANCES["bias_entries_off_share"] < 0.5
    assert departures.BELOW_THE_NOISE_ON_THE_CHIP <= set(departures.ALL)
    shares = departures.held_shares([[[0, 1, 2], [3, 4, 15]]], 16, 8)
    assert shares.tolist() == [[2 / 6, 2 / 6, 1 / 6, 0, 0, 0, 0, 1 / 6]]
