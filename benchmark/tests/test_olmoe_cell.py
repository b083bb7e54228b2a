"""The OLMoE cell's pieces that need no chip: shape functions against counts
made by hand, the token generator, the HLO-text scope map and the readers of
the per-layer metrics on a made-up run."""

import numpy as np

from benchmark import common

flops = common.load_module("flops", "olmoe")
driver = common.load_module("drivers", "resident_lm")


def _published():
    return common.model_params(common.load_json("configs", "olmoe-1b-7b.json"))


def test_parameter_counts_by_hand():
    p = _published()
    layer = 4 * 2048 ** 2 + 4 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024
    assert layer == 419_569_664
    assert flops.parameter_count(p) == layer + 2 * 50304 * 2048 + 2048 == 625_616_896
    # a token multiplies attention's four matrices, the router, 8 experts, the head
    active = 4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024 + 2048 * 50304
    assert flops.active_parameter_count(p) == active == 170_262_528
    assert flops.optimizer_bytes(p) == 7 * 4 * 625_616_896


def test_a_sample_is_4_39_tflop():
    p = _published()
    attention = 6 * 2 * 4096 * 4096 * 2048 / 2
    assert flops.attention_flops_per_sample(p, 4096) == attention
    assert flops.model_flops_per_sample(p, 4096) == 6 * 170_262_528 * 4096 + attention
    assert 4.38e12 < flops.model_flops_per_sample(p, 4096) < 4.40e12
    # ISSUE 25's formula for the grouped matmuls: 3 x 2 x 32 768 x 3 x 2048 x 1024
    assert flops.expert_matmul_flops_per_sample(p, 4096) == 3 * 2 * 32768 * 3 * 2048 * 1024


def test_tiny_hand_count():
    p = {"vocab_size": "10", "hidden_size": "4", "num_hidden_layers": "2",
         "num_attention_heads": "2", "intermediate_size": "3", "num_experts": "5",
         "num_experts_per_tok": "2"}
    layer = 4 * 16 + 4 * 4 + 4 * 5 + 5 * 3 * 4 * 3
    assert flops.parameter_count(p) == 2 * layer + 2 * 10 * 4 + 4
    assert flops.active_parameter_count(p) == 2 * (64 + 20 + 2 * 36) + 40
    assert flops.step_bytes(p, 3, 7) == 28 * flops.parameter_count(p) \
        + 8 * flops.parameter_count(p) + 4 * 3 * 7 * 10 * 4


def test_tokens_are_a_function_of_the_seed():
    a = driver.tokens_from_seed(2 ** 31 + 5, 4, 64, 50304, 1.0)
    b = driver.tokens_from_seed(2 ** 31 + 5, 4, 64, 50304, 1.0)
    c = driver.tokens_from_seed(2 ** 31 + 6, 4, 64, 50304, 1.0)
    assert a.shape == (4, 65) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert np.mean(a == c) < 0.1
    assert a.min() >= 0 and a.max() < 50304


def test_tokens_follow_zipf():
    toks = driver.tokens_from_seed(7, 64, 4096, 50304, 1.0)
    counts = np.sort(np.bincount(toks.ravel(), minlength=50304))[::-1]
    harmonic = np.sum(1.0 / np.arange(1, 50305))
    assert abs(counts[0] / toks.size - 1 / harmonic) < 0.01       # the commonest id
    assert abs(counts[:10].sum() / toks.size - np.sum(1 / np.arange(1, 11)) / harmonic) < 0.01
    # scrambled: the commonest ids are not the smallest
    assert np.argsort(-np.bincount(toks.ravel(), minlength=50304))[:10].max() > 100


HLO = '''
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %add.9 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/closed_call/optimizer/add"}
}
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/closed_call/optimizer/add" source_file="x.py"}
  %flash_attention_fwd.3 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(OLMoE)/olmoe/attn/pallas_call"}
  %flash_attention_bwd_dq.3 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(OLMoE))/olmoe/attn/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(OLMoE))/olmoe/moe/dispatch/jit(_take)/gather"}
  %ragged-dot-none.2 = f32[65536,1024]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.8 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc2, metadata={op_name="jit(f)/transpose(jvp(olmoe/head_loss))/mul"}
  %fusion.9 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc3, metadata={op_name="jit(f)/jvp(OLMoE)/olmoe/jit(_take)/gather"}
  %copy.4 = f32[8]{0} copy(%d)
}
'''


def test_scope_map_from_hlo_text():
    scopes = driver.scope_map(HLO)
    assert scopes["fusion.1"] == "optimizer"
    assert scopes["flash_attention_fwd.3"] == "olmoe/attn"
    assert scopes["fusion.7"] == "olmoe/moe/dispatch"
    assert scopes["ragged-dot-none.2"] == "olmoe/moe/experts"
    assert scopes["fusion.8"] == "olmoe/head_loss"
    assert scopes["fusion.9"] == "olmoe"
    assert "copy.4" not in scopes
    assert driver.scope_of("jit(f)/my_optimizer_thing/add") is None


def _run():
    per_op_s = {
        "%fusion.1 = f32[8]{0} fusion(%a), kind=kLoop": 0.040,
        "%flash_attention_fwd.3 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q)": 0.010,
        "%flash_attention_bwd_dq.3 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q)": 0.020,
        "%fusion.7 = f32[8]{0} fusion(%b)": 0.006,
        "%ragged-dot-none.2 = f32[65536,1024]{1,0} custom-call(%x, %w)": 0.050,
        "%fusion.8 = f32[8]{0} fusion(%c)": 0.080,
        "%copy.4 = f32[8]{0} copy(%d)": 0.002,
    }
    scopes = driver.scope_map(HLO)
    trace = {"steps": 2, "busy_s": 0.2, "window_s": 0.21,
             "scope_s": driver.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver.seconds_by_kernel(per_op_s, "flash_attention")}
    return {"trace": trace, "peaks": {"bf16_flops_per_s": 197e12},
            "shape": {"expert_matmul_flops_per_step": 2.0 * 1.2369e12,
                      "attention_flops_per_step": 2.0 * 0.2062e12}}


def test_layer_metric_readers():
    run = _run()
    read = lambda name: common.load_module("layer_metrics", name).read(run)
    assert run["trace"]["scope_s"]["unattributed"] == 0.002
    assert abs(read("optimizer_ms") - 20.0) < 1e-9
    assert abs(read("head_loss_ms") - 40.0) < 1e-9
    assert abs(read("moe_ms") - 28.0) < 1e-9              # dispatch 3 + experts 25
    assert abs(read("moe_dispatch_ms") - 3.0) < 1e-9
    assert abs(read("attn_ms") - 15.0) < 1e-9             # the kernels, by name
    assert abs(read("moe_gmm_roofline") - 100 * (2 * 1.2369e12 / 197e12) / 0.025) < 1e-6
    assert abs(read("attn_roofline") - 100 * (2 * 0.2062e12 / 197e12) / 0.015) < 1e-6


def test_readers_return_nothing_where_the_program_has_no_scopes():
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None}):
        for name in ("moe_ms", "moe_dispatch_ms", "moe_gmm_roofline", "attn_ms",
                     "attn_roofline", "head_loss_ms", "optimizer_ms"):
            assert common.load_module("layer_metrics", name).read(run) is None
