"""The Mellum2 cell's pieces that need no chip: the configuration file against
the catalog's published keys, shape functions against counts made by hand
(attention's visible pairs against a brute-force count), the HLO-text scope
map with the two kinds of attention layer told apart, the readers of the
per-layer metrics on a made-up run, the check's way with the auxiliary term,
and a CPU rehearsal of the whole cell at the tiny preset."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common

flops = common.load_module("flops", "mellum")
reference = common.load_module("reference", "mellum")
driver = common.load_module("drivers", "resident_lm_stateless")

CELL = "mellum2-12b-a2.5b.resident-16k"
NEW_METRICS = ("swa_ms", "swa_attn_ms", "swa_attn_roofline", "global_attn_ms",
               "global_attn_roofline", "held16_moe_ms")
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")


def _config():
    return common.load_json("configs", "mellum2-12b-a2.5b.json")


def _cut():
    return common.model_params(_config())


def _uncut():
    return dict(_cut(), vocab_size="98304", num_hidden_layers="28", num_experts="64")


def test_configuration_file_keeps_every_published_key():
    row, config = _catalog(), _config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    params = _cut()
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "rms_norm_eps", "vocab_size", "num_hidden_layers"):
        assert float(params[key]) == float(config[key]), key
    full = config["rope_parameters"]["full_attention"]
    assert float(params["rope_theta"]) == full["rope_theta"] \
        == config["rope_parameters"]["sliding_attention"]["rope_theta"]
    assert [float(params[k]) for k in ("rope_factor", "original_max_position_embeddings",
                                       "beta_fast", "beta_slow", "attention_factor")] == [
        full[k] for k in ("factor", "original_max_position_embeddings", "beta_fast",
                          "beta_slow", "attention_factor")]
    period = int(params["sliding_period"])
    assert config["layer_types"] == [
        "full_attention" if (l + 1) % period == 0 else "sliding_attention" for l in range(28)]


def test_configuration_file_states_the_cut():
    config = _config()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"] == {
        "num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304,
        "parameters": 12_149_915_904, "active_parameters": 2_439_053_568}
    params = _cut()
    assert params["router_experts"] == "64" and int(params["first_expert"]) % 16 == 0
    assert params["warmup_steps"] == str(10_485_760_000 // (4 * 16384)) == "160000"
    assert "4 chips share each layer" in config["deployment"]
    assert "seven pipeline stages" in config["deployment"]
    for key in ("sliding_mask", "yarn", "no_qk_norm_no_bias", "no_mtp", "aux_loss",
                "optimizer", "init", "held_share"):
        assert key in config["assumed"], key
    assert "precision" in config and "changed" in config
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-16k.json")
    want = {"seq_len": 16384, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 0,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_stateless", "rehearse": "tiny-lm-mellum"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert common.load_json("cardinalities", "mellum2-vocab-slice.json")["vocab_size"] == 24576
    tiny = common.load_json("rehearse", "tiny-lm-mellum.json")
    assert tiny["model_params"]["num_hidden_layers"] == 4
    assert tiny["model_params"]["sliding_window"] < tiny["traffic"]["seq_len"]
    assert (tiny["model_params"]["num_experts"], tiny["model_params"]["router_experts"]) == (4, 16)


def test_parameter_counts_by_hand():
    attention = 2304 * (4096 + 2 * 512) + 4096 * 2304
    expert = 3 * 2304 * 896
    rest = attention + 2 * 2304 + 2304 * 64
    assert (attention, expert, rest + 64 * expert) == (21_233_664, 6_193_152, 417_747_456)
    cut = 4 * (rest + 16 * expert) + 2 * 24576 * 2304 + 2304
    assert flops.parameter_count(_cut()) == cut == 595_153_152
    uncut = 28 * (rest + 64 * expert) + 2 * 98304 * 2304 + 2304
    assert flops.parameter_count(_uncut()) == uncut == 12_149_915_904
    assert flops.active_parameter_count(_uncut()) == 2_439_053_568
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * cut
    assert 16 * cut / 2 ** 30 > 0.25 * 15.75                  # the driver's floor
    assert round(16 * cut / 2 ** 30, 2) == 8.87


@pytest.mark.parametrize("seq_len,window", [
    (1, 1), (7, 1), (7, 3), (16, 16), (16, 17), (37, 8), (64, 5), (200, 64), (130, None)])
def test_visible_pairs_against_a_brute_force_count(seq_len, window):
    count = sum(1 for i in range(seq_len) for j in range(seq_len)
                if j <= i and (window is None or j > i - window))
    assert flops.visible_pairs(seq_len, window) == count


def test_a_step_is_9_28_tflop_forward_and_attention_62_percent_of_it():
    p, t = _cut(), 16384
    assert flops.visible_pairs(t, 1024) == 16_253_440
    assert flops.visible_pairs(t) == 134_225_920
    assert round(134_225_920 / 16_253_440, 2) == 8.26
    attn = flops.attention_flops(p, t)
    assert attn == {"sliding": 6.0 * 2 * 128 * 32 * 3 * 16_253_440,
                    "full": 6.0 * 2 * 128 * 32 * 134_225_920}
    assert flops.expected_held_pairs(p, t) == 32768          # 2048 a held expert
    held = 6 * 4 * 32768 * 3 * 2304 * 896
    every_token = 4 * (21_233_664 + 2304 * 64) + 2304 * 24576
    total = flops.model_flops_per_sample(p, t)
    assert total == 6 * every_token * t + held + attn["sliding"] + attn["full"]
    assert 9.27e12 < total / 3 < 9.29e12
    blocks = lambda kind, layers: 6 * layers * 21_233_664 * t + attn[kind]
    assert 0.61 < (blocks("sliding", 3) + blocks("full", 1)) / total < 0.63
    assert 0.30 < blocks("sliding", 3) / total < 0.32
    assert 0.30 < blocks("full", 1) / total < 0.32
    assert 0.17 < held / total < 0.18
    assert 0.19 < 6 * 2304 * 24576 * t / total < 0.21
    shape = flops.shape(p, 1, t)
    assert shape["swa_attention_flops_per_step"] == attn["sliding"]
    assert shape["global_attention_flops_per_step"] == attn["full"]
    assert shape["held_expert_matmul_flops_per_step"] == held
    assert shape["parameters"] == 595_153_152 and shape["seq_len"] == t
    assert flops.shape(p, 1, t, 1000.0)["held_expert_matmul_flops_per_step"] \
        == 6 * 1000 * 3 * 2304 * 896


HLO = '''
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/closed_call/optimizer/add"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/sliding/qkv/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Mellum))/mellum/checkpoint/rematted_computation/sliding/rope/mul"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Mellum))/mellum/checkpoint/full/qkv/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/sliding/mul"}
  %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/full/out/dot_general"}
  %flash_attention_swa_fwd.3 = bf16[1,32,16384,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/sliding/attn/pallas_call"}
  %flash_attention_swa_bwd_dkv.3 = bf16[1,4,16384,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(Mellum))/mellum/checkpoint/sliding/attn/pallas_call"}
  %flash_attention_fwd.1 = bf16[1,32,16384,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/full/attn/pallas_call"}
  %flash_attention_bwd_dq.1 = bf16[1,32,16384,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(Mellum))/mellum/checkpoint/full/attn/pallas_call"}
  %fusion.20 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/full/attn/transpose"}
  %fusion.7 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Mellum))/mellum/checkpoint/moe/dispatch/gather"}
  %fusion.9 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/moe/router/dot_general"}
  %grouped_matmul.2 = bf16[32768,896]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Mellum)/mellum/checkpoint/moe/while/body/experts/pallas_call"}
  %fusion.10 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(mellum/head_loss))/mul"}
  %fusion.12 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/head_loss/dot_general"}
  %fusion.15 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/embed/gather"}
  %fusion.16 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Mellum)/mellum/cos"}
  %copy.4 = f32[8]{0} copy(%d)
}
'''
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "mellum/sliding/qkv", "fusion.3": "mellum/sliding/rope",
    "fusion.4": "mellum/full/qkv", "fusion.5": "mellum/sliding", "fusion.6": "mellum/full/out",
    "flash_attention_swa_fwd.3": "mellum/sliding/attn",
    "flash_attention_swa_bwd_dkv.3": "mellum/sliding/attn",
    "flash_attention_fwd.1": "mellum/full/attn", "flash_attention_bwd_dq.1": "mellum/full/attn",
    "fusion.20": "mellum/full/attn",
    "fusion.7": "mellum/moe/dispatch", "fusion.9": "mellum/moe/router",
    "grouped_matmul.2": "mellum/moe/experts", "fusion.10": "mellum/head_loss",
    "fusion.12": "mellum/head_loss", "fusion.15": "mellum/embed", "fusion.16": "mellum"}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_two_kinds_of_layer_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    seconds = {"fusion.1": 0.050, "fusion.2": 0.040, "fusion.3": 0.020, "fusion.4": 0.010,
               "fusion.5": 0.002, "fusion.6": 0.004, "flash_attention_swa_fwd.3": 0.016,
               "flash_attention_swa_bwd_dkv.3": 0.024, "flash_attention_fwd.1": 0.030,
               "flash_attention_bwd_dq.1": 0.050, "fusion.20": 0.006, "fusion.7": 0.006,
               "fusion.9": 0.004, "grouped_matmul.2": 0.030, "fusion.10": 0.034,
               "fusion.12": 0.012, "fusion.15": 0.001, "fusion.16": 0.001, "copy.4": 0.002}
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in seconds.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.4, "window_s": 0.41,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention"),
             "kernel_s": driver.kernel_seconds(per_op_s, scopes, driver.KERNEL_PREFIXES)}
    return {"trace": trace, "window": {"step_ms": 205.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"swa_attention_flops_per_step": 2.4e12,
                      "global_attention_flops_per_step": 6.6e12}}


def test_kernel_seconds_are_kept_by_scope_and_by_the_longest_prefix():
    assert _run()["trace"]["kernel_s"] == {
        "mellum/sliding/attn": {"flash_attention_swa": pytest.approx(0.040)},
        "mellum/full/attn": {"flash_attention": pytest.approx(0.080)}}


@pytest.mark.parametrize("name,want", [
    ("swa_ms", 51.0),             # qkv 20 + rope 10 + the scope itself 1 + the kernels 8 + 12
    ("swa_attn_ms", 20.0),        # the windowed kernels alone, by name
    ("swa_attn_roofline", 100 * (2.4e12 / 197e12) / 0.020),
    ("global_attn_ms", 40.0),     # the full layer's kernels, not its transposes
    ("global_attn_roofline", 100 * (6.6e12 / 197e12) / 0.040),
    ("held16_moe_ms", 20.0),      # dispatch 3 + router 2 + experts 15
    ("step_ms", 205.0),           # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 0.4 / 0.41))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.002
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell (its driver reduces no `kernel_s`),
    and this program in a cell of another model."""
    read = common.load_module("layer_metrics", name).read
    glm_like = {"trace": {"steps": 2, "scope_s": {"glm4_moe_lite/mla/attn": 1.0},
                          "flash_attention_s": 0.5},
                "shape": {"mla_attention_flops_per_step": 1.0},
                "peaks": {"bf16_flops_per_s": 1.0}}
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0}, "kernel_s": {},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None},
                glm_like):
        assert read(run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_per_layer_entry_is_bound_to_the_cell(name):
    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "samples_per_s_per_chip" and entry["source"] == "device_trace"
    layers = {m["layer"] for m in bench["per_layer"] if CELL not in m.get("workloads", [CELL])}
    assert entry["layer"] in layers            # a layer BENCHMARK.json already names
    resolved = common.resolve_cell(CELL)
    assert {m["name"] for m in resolved["per_layer"]} == set(NEW_METRICS) | {
        "step_ms", "device_idle_pct"}
    assert resolved["cell"]["chips"] == 1 and resolved["traffic"]["name"] == "resident-lm-16k"
    assert len(resolved["cell"]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


class _Terms(driver.StatelessStepCheck):
    """The check's way with the terms of a loss, the reference's steps and the
    leaves' comparison made up."""

    def __init__(self, got, want):
        self.ref, self.want_terms = reference, want
        self.got = {"terms": got, "losses": None, "routings": []}
        self.params0 = {}

    def reference_steps(self):
        return {}

    def expert_figures(self, want, tolerances):
        return {}, []


@pytest.mark.parametrize("off,ok", [(0.0, True), (1e-3, True), (3e-2, False)])
def test_the_auxiliary_term_is_held_to_a_limit_of_its_own(off, ok, monkeypatch):
    """A load-balance term of 0.04 beside a cross entropy of 10 is 0.4% of the
    sum: wrong by 3% it moves the sum by 1.2e-4, inside the sum's limit."""
    from benchmark import check_lm

    monkeypatch.setattr(check_lm, "compare",
                        lambda got, want, params0, tolerances: {
                            "ok": True, "failures": [], "figures": {}})
    want = {"loss_ce": np.array([10.1, 10.0]), "loss_aux": np.array([0.0404, 0.0402])}
    got = {"loss_ce": want["loss_ce"].copy(),
           "loss_aux": want["loss_aux"] * np.array([1.0, 1.0 + off])}
    monkeypatch.setattr(reference, "TOLERANCES", {
        "loss_ce_rel": 3e-4, "loss_aux_rel": 2e-3, "mu_rel_l2": {}, "update_rel_l2": {}})
    verdict = _Terms(got, want).compare()
    assert verdict["ok"] == ok
    assert abs(verdict["figures"]["loss_aux_rel"] - off) < 1e-9
    assert verdict["figures"]["loss_ce_rel"] == 0.0
    assert bool(verdict["failures"]) != ok


def test_read_program_takes_the_auxiliary_term_as_the_sum_less_the_zoo_s_terms():
    check = driver.StatelessStepCheck.__new__(driver.StatelessStepCheck)

    class State:
        params = {}

        class opt_state:
            mu, nu = {}, {}

    metrics = [{"loss": np.array([10.14]), "loss_ce": np.array([10.1])},
               {"loss": np.array([10.04]), "loss_ce": np.array([10.0])}]
    check.read_program(State, metrics, [])
    np.testing.assert_allclose(check.got["terms"]["loss_aux"], [0.04, 0.04], atol=1e-12)
    np.testing.assert_allclose(check.got["losses"], [10.14, 10.04])


def test_the_yarn_blend_by_hand():
    """low = 18 and high = 35 for theta = 500 000, L0 = 8192; a = 0.1 ln 16 + 1."""
    hp = reference.hyper(_cut())
    freq, factor = reference.frequencies(True, hp)
    plain, one = reference.frequencies(False, hp)
    ratio = np.asarray(freq) / np.asarray(plain)
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)
    np.testing.assert_allclose(ratio[27], 1 - (9 / 17) * (15 / 16), rtol=1e-6)
    assert (factor, one) == (pytest.approx(0.1 * math.log(16) + 1, abs=1e-15), 1.0)
    np.testing.assert_allclose(np.asarray(plain), 500000.0 ** (-np.arange(64) / 64), rtol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(common.BENCH_DIR, "reference", "mellum.py")) as f:
        text = f.read()
    assert "elasticdl_tpu" not in text.split('"""', 2)[2]
    assert "import model_zoo" not in text and "from model_zoo" not in text


def test_a_cpu_rehearsal_of_the_cell_reads_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "3000000017", "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=common.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["rehearsal"] is True
    assert set(line["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    assert "attn/kv_block_visits" in out.stdout and "pairs_held_share" in out.stdout
