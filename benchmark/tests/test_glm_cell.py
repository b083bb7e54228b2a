"""The GLM-4.7-Flash cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand, the HLO-text scope map with the module's own layer told from the main
stream's, the readers of the per-layer metrics on a made-up run, and the
check's way with a loss that is a sum of terms."""

import json

import numpy as np
import pytest

from benchmark import common

flops = common.load_module("flops", "glm4_moe_lite")
reference = common.load_module("reference", "glm4_moe_lite")
driver = common.load_module("drivers", "resident_lm_model")

CELL = "glm-4.7-flash.resident-8k"
NEW_METRICS = ("mla_ms", "mla_attn_ms", "mla_attn_roofline", "held_moe_ms",
               "held_gmm_roofline", "mtp_ms", "head_loss_ms")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# the catalog row's `config` (architectures.jsonl, GLM-4.7-Flash)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20,
    "n_group": 1, "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4, "first_k_dense_replace": 1,
    "num_hidden_layers": 47, "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}


def _cut():
    return common.model_params(common.load_json("configs", "glm-4.7-flash.json"))


def _uncut():
    return dict(_cut(), vocab_size="154880", num_hidden_layers="47", n_routed_experts="64")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_keeps_the_published_key(key):
    config = common.load_json("configs", "glm-4.7-flash.json")
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    # and the program is built with it, where the program has such a key
    params = _cut()
    if key in params:
        assert float(params[key]) == float(config[key])


def test_configuration_file_states_the_cut():
    config = common.load_json("configs", "glm-4.7-flash.json")
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880,
        "parameters": 29_943_390_976, "parameters_with_the_mtp_module": 30_587_097_088}
    params = _cut()
    assert (params["router_experts"], params["first_expert"]) == ("64", "0")
    assert params["warmup_steps"] == str(10_485_760_000 // (8 * 8192)) == "160000"
    assert "8 chips share each layer" in config["deployment"]
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"].startswith(
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-mtp-8k.json")
    want = {"seq_len": 8192, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "trace_dispatches": 2,
            "zipf_s": 1.0, "generator": "zipf-tokens", "driver": "resident_lm_model",
            "rehearse": "tiny-lm-model"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert common.load_json("cardinalities", "glm-vocab-slice.json")["vocab_size"] == 19360


def test_parameter_counts_by_hand():
    mla = 2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512 + 512 * 8960 + 5120 * 2048
    dense = mla + 3 * 2048 * 10240 + 2 * 2048
    expert = 3 * 2048 * 1536
    sparse_rest = mla + expert + 2048 * 64 + 2 * 2048
    assert (mla, dense, expert, sparse_rest) == (
        21_759_232, 84_677_888, 9_437_184, 31_331_584)
    module = sparse_rest + 8 * expert + 4096 * 2048 + 3 * 2048
    cut = dense + 4 * (sparse_rest + 8 * expert) + 2 * 19360 * 2048 + 2048
    assert flops.parameter_count(_cut(), with_mtp=False) == cut == 591_294_720
    assert flops.parameter_count(_cut()) == cut + module == 706_518_528
    uncut = dense + 46 * (sparse_rest + 64 * expert) + 2 * 154880 * 2048 + 2048
    assert flops.parameter_count(_uncut(), with_mtp=False) == uncut == 29_943_390_976
    assert flops.parameter_count(_uncut()) == 30_587_097_088          # the card's 30B
    assert 3.5e9 < flops.active_parameter_count(_uncut()) < 3.7e9     # A3B, and the head
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * 706_518_528
    assert 16 * 706_518_528 / 2 ** 30 > 0.25 * 15.75                  # the driver's floor


def test_a_step_is_29_7_tflop_and_latent_attention_63_percent_of_it():
    p = _cut()
    t = 8192
    mla_matmul = 21_759_232 - 768 - 512                   # without the two latent norms
    attention = 6 * 6 * 2 * t * t * 20 * 256 / 2          # six blocks: five layers and the module
    assert flops.attention_flops_per_sample(p, t) == attention
    assert flops.expected_held_pairs(p, t) == 4096
    held = 6 * 5 * 4096 * 3 * 2048 * 1536
    assert flops.held_expert_matmul_flops(p, 5 * 4096) == held
    every_token = (6 * mla_matmul + 3 * 2048 * 10240 + 5 * (3 * 2048 * 1536 + 2048 * 64)
                   + 2 * 2048 * 2048 + 2 * 2048 * 19360)
    assert flops.model_flops_per_sample(p, t) == 6 * every_token * t + held + attention
    assert 29.6e12 < flops.model_flops_per_sample(p, t) < 29.8e12
    latent = 6 * 6 * mla_matmul * t + attention
    assert 0.62 < latent / flops.model_flops_per_sample(p, t) < 0.64
    shape = flops.shape(p, 1, t)
    assert shape["mla_attention_flops_per_step"] == attention
    assert shape["held_expert_matmul_flops_per_step"] == held
    assert shape["parameters"] == 706_518_528 and shape["seq_len"] == t
    # counted pairs take the place of the even share
    assert flops.shape(p, 1, t, 1000.0)["held_expert_matmul_flops_per_step"] \
        == 6 * 1000 * 3 * 2048 * 1536


HLO = '''
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/closed_call/optimizer/add"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/checkpoint/mla/q_lora/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Glm4MoeLite))/glm4_moe_lite/checkpoint/rematted_computation/mla/rope/mul"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Glm4MoeLite))/glm4_moe_lite/mtp/checkpoint/mla/kv_lora/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/checkpoint/mla/mul"}
  %flash_attention_fwd.3 = bf16[1,20,8192,256]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/checkpoint/mla/attn/pallas_call"}
  %flash_attention_bwd_dkv.3 = bf16[1,20,8192,256]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(Glm4MoeLite))/glm4_moe_lite/mtp/checkpoint/mla/attn/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Glm4MoeLite))/glm4_moe_lite/checkpoint/moe/dispatch/gather"}
  %fusion.8 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/checkpoint/moe/shared/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/mtp/checkpoint/moe/router/dot_general"}
  %grouped_matmul.2 = bf16[8192,1536]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/checkpoint/moe/while/body/experts/pallas_call"}
  %grouped_matmul.5 = bf16[8192,1536]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/mtp/checkpoint/moe/while/body/experts/pallas_call"}
  %fusion.10 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(glm4_moe_lite/head_loss))/mul"}
  %fusion.11 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(glm4_moe_lite/mtp/head_loss)/reduce_max"}
  %fusion.12 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/mtp/head_loss/dot_general"}
  %fusion.13 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/mtp/join/concatenate"}
  %fusion.14 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/checkpoint/dense_mlp/dot_general"}
  %fusion.15 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Glm4MoeLite)/glm4_moe_lite/embed/gather"}
  %copy.4 = f32[8]{0} copy(%d)
}
'''
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "glm4_moe_lite/mla/q_lora",
    "fusion.3": "glm4_moe_lite/mla/rope", "fusion.4": "glm4_moe_lite/mtp/mla/kv_lora",
    "fusion.5": "glm4_moe_lite/mla", "flash_attention_fwd.3": "glm4_moe_lite/mla/attn",
    "flash_attention_bwd_dkv.3": "glm4_moe_lite/mtp/mla/attn",
    "fusion.7": "glm4_moe_lite/moe/dispatch", "fusion.8": "glm4_moe_lite/moe/shared",
    "fusion.9": "glm4_moe_lite/mtp/moe/router",
    "grouped_matmul.2": "glm4_moe_lite/moe/experts",
    "grouped_matmul.5": "glm4_moe_lite/mtp/moe/experts",
    "fusion.10": "glm4_moe_lite/head_loss", "fusion.11": "glm4_moe_lite/mtp/head_loss",
    "fusion.12": "glm4_moe_lite/mtp/head_loss", "fusion.13": "glm4_moe_lite/mtp/join",
    "fusion.14": "glm4_moe_lite/dense_mlp", "fusion.15": "glm4_moe_lite/embed"}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_module_s_layer_from_the_main_stream_s(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    seconds = {"fusion.1": 0.050, "fusion.2": 0.040, "fusion.3": 0.020, "fusion.4": 0.010,
               "fusion.5": 0.002, "flash_attention_fwd.3": 0.016,
               "flash_attention_bwd_dkv.3": 0.024, "fusion.7": 0.006, "fusion.8": 0.060,
               "fusion.9": 0.004, "grouped_matmul.2": 0.030, "grouped_matmul.5": 0.010,
               "fusion.10": 0.034, "fusion.11": 0.004, "fusion.12": 0.012,
               "fusion.13": 0.008, "fusion.14": 0.070, "fusion.15": 0.001, "copy.4": 0.002}
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in seconds.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.4, "window_s": 0.41,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "window": {"step_ms": 205.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"mla_attention_flops_per_step": 12.37e12,
                      "held_expert_matmul_flops_per_step": 1.16e12}}


@pytest.mark.parametrize("name,want", [
    ("mla_ms", 56.0),         # q_lora 20 + rope 10 + mtp kv_lora 5 + mla 1 + the kernels 8 + 12
    ("mla_attn_ms", 20.0),    # the kernels, by name, both streams
    ("mla_attn_roofline", 100 * (12.37e12 / 197e12) / 0.020),
    ("held_moe_ms", 25.0),    # dispatch 3 + mtp router 2 + experts 15 + 5; not the shared expert
    ("held_gmm_roofline", 100 * (1.16e12 / 197e12) / 0.020),
    ("mtp_ms", 36.0),         # kv_lora 5 + kernel 12 + router 2 + experts 5 + head 2 + 6 + join 4
    ("head_loss_ms", 25.0),   # main 17, the module's 2 + 6
    ("step_ms", 205.0),       # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 0.4 / 0.41)),
    ("optimizer_ms", 25.0)])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.002
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell, and this program in an OLMoE run."""
    olmoe_like = {"trace": {"steps": 2, "scope_s": {"olmoe/attn": 1.0, "olmoe/moe": 1.0},
                            "flash_attention_s": 0.5},
                  "shape": {"attention_flops_per_step": 1.0}, "peaks": {"bf16_flops_per_s": 1.0}}
    read = common.load_module("layer_metrics", name).read
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None}):
        if name in FOLDED and set((run["trace"] or {}).get("scope_s", ())) - {"unattributed"}:
            continue
        assert read(run) is None
    # the flash kernel's calls are found by name in any program that runs
    # them: `workloads` in BENCHMARK.json is what binds the entry to its cell
    assert read(olmoe_like) == (250.0 if name == "mla_attn_ms" else None)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_per_layer_entry_is_bound_to_the_cell(name):
    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"] if name in FOLDED else entry["workloads"] == [CELL]
    assert entry["moves"] == "samples_per_s_per_chip" and entry["source"] == "device_trace"
    resolved = common.resolve_cell(CELL)
    assert {m["name"] for m in resolved["per_layer"]} == set(NEW_METRICS) | {
        "step_ms", "device_idle_pct"}
    assert resolved["cell"]["chips"] == 1 and resolved["traffic"]["name"] == "resident-lm-mtp-8k"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and len(bench["workloads"]) == 7


class _Terms(driver.ModelStepCheck):
    """The check's way with the terms of a loss, the reference's steps made up."""

    def __init__(self, got, want):
        self.ref, self.got, self.want_terms = reference, {"terms": got}, want

    def reference_steps(self):
        raise AssertionError("not reached")


@pytest.mark.parametrize("off,ok", [(0.0, True), (1e-4, True), (1e-3, False)])
def test_each_term_of_the_loss_is_held_to_its_own_limit(off, ok, monkeypatch):
    """`loss` could agree while its terms do not (a weight of 0.3 hides 70%
    of the module's error): each term is compared at every step."""
    base = {"ok": True, "failures": [], "figures": {}}
    monkeypatch.setattr(driver._share.ShareStepCheck, "compare",
                        lambda self: {k: (dict(v) if isinstance(v, dict) else list(v)
                                          if isinstance(v, list) else v)
                                      for k, v in base.items()})
    want = {"loss_main": np.array([9.9, 9.8]), "loss_mtp": np.array([9.9, 9.7])}
    got = {"loss_main": want["loss_main"].copy(),
           "loss_mtp": want["loss_mtp"] * np.array([1.0, 1.0 + off])}
    monkeypatch.setattr(reference, "TOLERANCES", {"loss_main_rel": 2e-4, "loss_mtp_rel": 2e-4})
    verdict = _Terms(got, want).compare()
    assert verdict["ok"] == ok
    assert abs(verdict["figures"]["loss_mtp_rel"] - off) < 1e-9
    assert verdict["figures"]["loss_main_rel"] == 0.0
    assert bool(verdict["failures"]) != ok


# ------------------------------------------------------------------ #
# cases ISSUE 32 asked for in test_flops.py and test_schema.py: those files
# are the accepted benchmark's and a model_config PR edits none of them


@pytest.mark.parametrize("model,config,active", [
    ("olmoe", "olmoe-1b-7b", 170_262_528),
    ("nemotron_h", "nemotron-3-nano-30b-a3b", 542_932_992),
    ("glm4_moe_lite", "glm-4.7-flash", 400_621_568)])
def test_lm_active_parameters_at_the_cut(model, config, active):
    """What a token's forward pass multiplies at each LM configuration's cut
    when every expert it chose is computed, by the configuration's own shape
    functions (the head once, no embedding, no norm)."""
    shapes = common.load_module("flops", model)
    params = common.model_params(common.load_json("configs", config + ".json"))
    assert shapes.active_parameter_count(params) == active


def test_every_configuration_is_run_by_some_cell_and_has_a_file_of_its_own():
    import os

    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    assert {w["config"] for w in bench["workloads"]} == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200 and len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert os.path.getsize(os.path.join(common.ROOT, "BENCHMARK.json")) <= 64 * 1024
