"""The Nemotron-H cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand, the HLO-text scope map with `jax.checkpoint`'s names in the paths, the
counters of the held share and the readers of the per-layer metrics on a
made-up run."""

import json

import numpy as np

from benchmark import common

flops = common.load_module("flops", "nemotron_h")
driver = common.load_module("drivers", "resident_lm_share")

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
NEW_METRICS = ("ssm_ms", "ssm_scan_ms", "ssm_scan_roofline", "moe_block_ms",
               "moe_routed_ms", "gqa_attn_ms", "gqa_attn_roofline")


def _cut():
    return common.model_params(common.load_json("configs", "nemotron-3-nano-30b-a3b.json"))


def _uncut():
    return dict(_cut(), vocab_size="131072", num_hidden_layers="52",
                hybrid_override_pattern=PUBLISHED_PATTERN, n_routed_experts="128")


def test_configuration_file_keeps_every_published_key_but_the_three_reduced():
    config = common.load_json("configs", "nemotron-3-nano-30b-a3b.json")
    published = {   # the catalog row's `config`, keys that are numbers or flags
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "intermediate_size": 1856, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
        "n_shared_experts": 1, "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 2, "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "topk_group": 1, "norm_topk_prob": True, "use_conv_bias": True,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "rope_theta": 10000,
        "hybrid_override_pattern": PUBLISHED_PATTERN}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    assert sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                                   "vocab_size": 131072, "parameters": 31_577_940_288}
    params = _cut()
    assert params["hybrid_override_pattern"] == PUBLISHED_PATTERN[:9] == "MEMEM*EME"
    assert (params["router_experts"], params["first_expert"]) == ("128", "0")
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-8k.json")
    want = {"seq_len": 8192, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "trace_dispatches": 2,
            "zipf_s": 1.0, "generator": "zipf-tokens", "driver": "resident_lm_share"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")


def test_parameter_counts_by_hand():
    mamba = 2688 * 10304 + 4096 * 2688 + 5 * 6144 + 3 * 64 + 2688 + 4096
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    expert = 2 * 2688 * 1856
    moe_rest = 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688      # with the selection bias
    assert (mamba, attn, expert, moe_rest) == (38_744_896, 23_399_040, 9_977_856, 20_302_592)
    cut = 4 * mamba + 4 * (moe_rest + 8 * expert) + attn + 2 * 16384 * 2688 + 2688
    assert flops.parameter_count(_cut()) == cut == 666_963_456
    assert flops.optimizer_parameter_count(_cut()) == cut - 4 * 128
    uncut = 23 * mamba + 23 * (moe_rest + 128 * expert) + 6 * attn + 2 * 131072 * 2688 + 2688
    assert flops.parameter_count(_uncut()) == uncut == 31_577_940_288        # the card's 31.6B
    assert 3.1e9 < flops.active_parameter_count(_uncut()) < 3.3e9            # and its A3.2B
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * (cut - 512)


def test_a_step_is_17_6_tflop():
    p = _cut()
    t = 8192
    mamba = 6 * 4 * t * (2688 * 10304 + 4096 * 2688)
    scan = 6 * 4 * (t * 128 * 8 * 128 + t * 128 * 4096 + 2 * t * 128 * 4096)
    assert flops.scan_flops_per_sample(p, t) == scan
    assert 0.33e12 < scan < 0.34e12
    attention = 6 * 2 * t * t * 32 * 128 / 2
    assert flops.attention_flops_per_sample(p, t) == attention
    assert flops.expected_held_pairs(p, t) == 3072
    held = 6 * 4 * 3072 * 2 * 2688 * 1856
    assert flops.held_expert_matmul_flops(p, 4 * 3072) == held
    dense = 6 * t * (4 * (2 * 2688 * 3712 + 2688 * 128) + 2 * 2688 * 4096
                     + 2 * 2688 * 256 + 2688 * 16384)
    assert flops.model_flops_per_sample(p, t) == mamba + scan + attention + held + dense
    assert 17.5e12 < flops.model_flops_per_sample(p, t) < 17.7e12
    # the scan is memory-bound by shape: x, B, C, Δ, y forward; the same, dy
    # and four gradients backward
    floats = (6144 + 64 + 4096) + (6144 + 64 + 4096 + 6144 + 64)
    assert flops.scan_bytes_per_sample(p, t) == 4 * 4 * t * floats
    assert flops.scan_bytes_per_sample(p, t) / 819e9 > scan / 197e12


HLO = '''
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/closed_call/optimizer/add"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(NemotronH)/nemotron_h/mamba/checkpoint/ssd/checkpoint/exp"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(NemotronH))/nemotron_h/mamba/checkpoint/rematted_computation/conv/mul"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(NemotronH))/nemotron_h/mamba/checkpoint/in_proj/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(NemotronH)/nemotron_h/mamba/checkpoint/mul"}
  %flash_attention_fwd.3 = bf16[1,32,8192,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(NemotronH)/nemotron_h/attn/checkpoint/pallas_call"}
  %flash_attention_bwd_dkv.3 = bf16[1,2,8192,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(NemotronH))/nemotron_h/attn/checkpoint/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(NemotronH))/nemotron_h/moe/checkpoint/dispatch/gather"}
  %fusion.8 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(NemotronH)/nemotron_h/moe/checkpoint/shared/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(NemotronH)/nemotron_h/moe/checkpoint/router/dot_general"}
  %ragged-dot-none.2 = bf16[6144,1856]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.10 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(nemotron_h/head_loss))/mul"}
  %fusion.11 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(NemotronH)/nemotron_h/jit(_take)/gather"}
  %copy.4 = f32[8]{0} copy(%d)
}
'''


def test_scope_map_reads_through_checkpoint_names():
    scopes = driver.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert scopes == {
        "fusion.1": "optimizer", "fusion.2": "nemotron_h/mamba/ssd",
        "fusion.3": "nemotron_h/mamba/conv", "fusion.4": "nemotron_h/mamba/in_proj",
        "fusion.5": "nemotron_h/mamba", "flash_attention_fwd.3": "nemotron_h/attn",
        "flash_attention_bwd_dkv.3": "nemotron_h/attn",
        "fusion.7": "nemotron_h/moe/dispatch", "fusion.8": "nemotron_h/moe/shared",
        "fusion.9": "nemotron_h/moe/router", "ragged-dot-none.2": "nemotron_h/moe/experts",
        "fusion.10": "nemotron_h/head_loss", "fusion.11": "nemotron_h"}
    assert driver.scope_of("jit(f)/my_optimizer_thing/add", flops.SCOPES) is None


def _run():
    per_op_s = {
        "%fusion.1 = f32[8]{0} fusion(%a)": 0.050,
        "%fusion.2 = f32[8]{0} fusion(%a)": 0.040,
        "%fusion.3 = f32[8]{0} fusion(%a)": 0.020,
        "%fusion.4 = f32[8]{0} fusion(%a)": 0.100,
        "%fusion.5 = f32[8]{0} fusion(%a)": 0.002,
        "%flash_attention_fwd.3 = bf16[1,32,8192,128]{3,2,1,0} custom-call(%q)": 0.016,
        "%flash_attention_bwd_dkv.3 = bf16[1,2,8192,128]{3,2,1,0} custom-call(%q)": 0.024,
        "%fusion.7 = f32[8]{0} fusion(%b)": 0.006,
        "%fusion.8 = f32[8]{0} fusion(%b)": 0.060,
        "%fusion.9 = f32[8]{0} fusion(%b)": 0.004,
        "%ragged-dot-none.2 = bf16[6144,1856]{1,0} custom-call(%x, %w)": 0.030,
        "%fusion.10 = f32[8]{0} fusion(%c)": 0.034,
        "%copy.4 = f32[8]{0} copy(%d)": 0.002,
    }
    scopes = driver.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.4, "window_s": 0.41,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"scan_flops_per_step": 0.335e12, "scan_bytes_per_step": 3.515e9,
                      "gqa_attention_flops_per_step": 1.649e12}}


def test_layer_metric_readers():
    run = _run()
    read = lambda name: common.load_module("layer_metrics", name).read(run)
    assert run["trace"]["scope_s"]["unattributed"] == 0.002
    assert abs(read("ssm_ms") - 81.0) < 1e-9          # in_proj 50 + ssd 20 + conv 10 + 1
    assert abs(read("ssm_scan_ms") - 30.0) < 1e-9     # conv + ssd (+ gate_norm, none here)
    assert abs(read("moe_block_ms") - 50.0) < 1e-9    # dispatch 3 + shared 30 + router 2 + experts 15
    assert abs(read("moe_routed_ms") - 20.0) < 1e-9   # without the shared expert
    assert abs(read("gqa_attn_ms") - 20.0) < 1e-9     # the kernels, by name
    # memory-bound: 3.515 GB / 819 GB/s = 4.29 ms of the 20 ms under ssd
    assert abs(read("ssm_scan_roofline") - 100 * (3.515e9 / 819e9) / 0.020) < 1e-6
    assert abs(read("gqa_attn_roofline") - 100 * (1.649e12 / 197e12) / 0.020) < 1e-6
    assert abs(read("optimizer_ms") - 25.0) < 1e-9    # the accepted reader, same run


def test_readers_return_nothing_where_the_program_has_no_scopes():
    """What the parent gives in any cell, and this program in an OLMoE run."""
    olmoe_like = {"trace": {"steps": 2, "scope_s": {"olmoe/attn": 1.0, "olmoe/moe": 1.0},
                            "flash_attention_s": 0.5},
                  "shape": {"attention_flops_per_step": 1.0}, "peaks": {"bf16_flops_per_s": 1.0}}
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None},
                olmoe_like):
        for name in NEW_METRICS:
            if run is olmoe_like and name == "gqa_attn_ms":
                continue
            assert common.load_module("layer_metrics", name).read(run) is None, name
    # the flash kernel's calls are found by name in any program that runs
    # them: `workloads` in BENCHMARK.json is what binds the entry to its cell
    assert common.load_module("layer_metrics", "gqa_attn_ms").read(olmoe_like) == 250.0


def test_held_load_counts_the_share():
    idx = np.zeros((2, 8, 3), np.int64)
    idx[0] = [[0, 1, 9]] * 4 + [[1, 2, 10]] * 4          # held (experts 0-3): 4 + 8 + 4
    idx[1] = [[9, 10, 11]] * 8                           # none held
    got = driver.held_load(idx, 16, (0, 4))
    assert got["held_most"] == 8 and got["held_fewest"] == 0 and got["held_empty"] == 5
    assert got["pairs_held_most_in_a_layer"] == 16
    assert got["pairs_held_share"] == 16 / 48


def test_new_per_layer_entries_are_bound_to_the_cell():
    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = "nemotron-3-nano-30b-a3b.resident-8k"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["moves"] == "samples_per_s_per_chip"
        assert by_name[name]["source"] == "device_trace"
    resolved = common.resolve_cell(cell)
    assert {m["name"] for m in resolved["per_layer"]} == set(NEW_METRICS) | {
        "step_ms", "device_idle_pct"}
    assert resolved["cell"]["chips"] == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
