"""The Phi-4-mini-flash cell's pieces that need no chip: the configuration
file against the catalog's published keys, shape functions against counts made
by hand, the HLO-text scope map with the layers' scopes told apart, the readers
of the eleven per-layer metrics on a made-up run, and the rehearsal's line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import common

flops = common.load_module("flops", "phi4flash")
reference = common.load_module("reference", "phi4flash")
driver = common.load_module("drivers", "resident_lm_plain")
departures = common.load_module("rehearse", "departures_phi4flash")

CELL = "phi-4-mini-flash.resident-8k"
NEW_METRICS = ("sambay_mamba_ms", "sambay_scan_ms", "sambay_scan_roofline", "sambay_gmu_ms",
               "sambay_diff_attn_ms", "sambay_diff_flash_ms", "sambay_diff_flash_roofline",
               "sambay_mlp_ms", "head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# the catalog row's `config` (architectures.jsonl, Phi-4-mini-flash-reasoning)
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 200064}
REDUCED = {"num_hidden_layers": 6, "vocab_size": 25008}


def _config():
    return common.load_json("configs", "phi-4-mini-flash.json")


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
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["vocab_size"] == 200064
    assert config["published"]["parameters"] == 3_852_562_944
    assert config["kept_layers"] == [0, 1, 16, 17, 18, 19]
    assert _cut()["kept_layers"] == "0,1,16,17,18,19"
    for figure in ("697 094 272", "11.15 GB", "3 852 562 944", "25 008"):
        assert figure in config["reduced"]["vocab_size"]
    for figure in ("119 895 040", "98 322 304", "104 867 840", "91 766 144"):
        assert figure in config["reduced"]["num_hidden_layers"]
        assert figure in config["published"]["parameters_note"]
    # OLMoE's warm-up in tokens over this deployment's tokens a step
    assert _cut()["warmup_steps"] == str(round(10_485_760_000 / (8 * 8192))) == "160000"
    assert "eight pipeline stages of four layers" in config["deployment"]
    assert "2 of 6 layers here against 8 of 32" in config["deployment"]
    assert "1 of 6 against 8 of 32" in config["deployment"]
    assert set(config["assumed"]) >= {
        "mamba_sizes", "mamba_layout", "memory_is_the_scan_output", "layer_arrangement",
        "differential_attention", "biases", "no_positions", "initialisation", "optimizer",
        "sequence"}
    for note in config["assumed"].values():
        assert "from the paper" in note or "from memory" in note
    assert set(config["changed"]) >= {"recomputation", "one_flash_call_for_both_maps",
                                      "selective_scan_kernels"}
    for stated in ("every LayerNorm", "selective scan's state", "FLOAT32 sum",
                   "bfloat16 operands"):
        assert stated in config["precision"]
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"].startswith(entry["source"]) and entry["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-sambay-8k.json")
    want = {"seq_len": 8192, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 0,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_plain", "rehearse": "tiny-lm-sambay"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert "ROADMAP R7" in traffic["why_this_batch"]
    vocab = common.load_json("cardinalities", "phi4flash-vocab-slice.json")
    assert (vocab["vocab_size"], vocab["zipf_s"], vocab["fields"]) == (25008, 1.0, [25008])
    assert 8 * 25008 == 200064
    tiny = common.load_json("rehearse", "tiny-lm-sambay.json")["model_params"]
    assert (tiny["num_hidden_layers"], tiny["kept_layers"]) == (6, "0,1,16,17,18,19")


def test_parameter_counts_by_hand():
    c, e, f, n, r, k = 2560, 5120, 10240, 16, 160, 4
    mlp_and_norms = 3 * c * f + 4 * c
    mamba = c * 2 * e + k * e + e + e * (r + 2 * n) + r * e + e + e * n + e + e * c + mlp_and_norms
    tail = c * c + c + 4 * 64 + 128                      # out-projection, bias, λ, sub-norm
    self_attention = c * 5120 + 5120 + tail + mlp_and_norms
    gmu = 2 * c * e + mlp_and_norms
    cross = c * c + c + tail + mlp_and_norms
    assert (mamba, self_attention, gmu, cross) == (
        119_895_040, 98_322_304, 104_867_840, 91_766_144)
    published = 9 * mamba + 9 * self_attention + 7 * gmu + 7 * cross + 200064 * c + 2 * c
    assert flops.parameter_count("published") == published == 3_852_562_944
    cut = 2 * mamba + 2 * self_attention + gmu + cross + 25008 * c + 2 * c
    assert flops.parameter_count(_cut()) == cut == 697_094_272
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * 697_094_272
    assert 0.65 < 16 * 697_094_272 / 2 ** 30 / 15.75 < 0.67              # 10.39 GiB of state
    # what the issue rules out: a quarter of the vocabulary is 12.2 GB of state
    assert 16 * flops.parameter_count({**_cut(), "vocab_size": "50016"}) > 12.1e9
    assert flops.layers_by_kind(_cut()) == dict(mamba=2, sliding=1, full=1, gmu=1, cross=1)


def test_a_step_s_forward_is_12_5_tflop_by_the_model():
    p, t = _cut(), 8192
    shape = flops.shape(p, 1, t)
    total = shape["model_flops_per_sample"]
    assert 12.4e12 < total / 3 < 12.6e12
    share = lambda key: shape[key] / total
    assert 0.61 < share("mlp_matmul_flops_per_step") < 0.63
    assert 0.08 < share("head_matmul_flops_per_step") < 0.09
    assert 0.20 < sum(share(f"{kind}_matmul_flops_per_step") for kind in flops.KINDS) < 0.22
    # both maps of 20 query pairs: q·kᵀ at 64 and p·v at 128, over the visible pairs
    full = t * (t + 1) // 2
    sliding = 512 * 513 // 2 + (t - 512) * 512
    assert flops.visible_pairs(t) == full and flops.visible_pairs(t, 512) == sliding
    assert shape["diff_flash_flops_per_step"] == 6 * 2 * (64 + 128) * 20 * (2 * full + sliding)
    assert 0.08 < share("diff_flash_flops_per_step") < 0.09
    # 0.67 G state updates a layer a pass; the planes the scan must move
    assert shape["s6_scan_updates_per_step"] == 2 * t * 5120 * 16 == 1_342_177_280
    plane, small = t * 5120, t * 16
    assert shape["s6_scan_bytes_per_step"] == 4 * 2 * (
        (3 * plane + 2 * small) + (4 * plane + 2 * small) + (2 * plane + 2 * small + 5120 * 17))
    assert shape["parameters"] == 697_094_272 and shape["seq_len"] == t


_OP = 'metadata={op_name="jit(f)/'
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", "jvp(Phi4Flash)/phi4flash/embed/gather"),
        ("fusion.3", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/norm/mul"),
        ("fusion.4", "fusion", "transpose(jvp(Phi4Flash))/phi4flash/checkpoint/rematted_computation/mamba/proj/dot_general"),
        ("causal_conv1d_fwd.1", "custom-call", "jvp(Phi4Flash)/phi4flash/checkpoint/mamba/conv/pallas_call"),
        ("fusion.5", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/mamba/dt/dot_general"),
        ("selective_scan_fwd.2", "custom-call", "jvp(Phi4Flash)/phi4flash/checkpoint/mamba/scan/pallas_call"),
        ("selective_scan_bwd.2", "custom-call", "transpose(jvp(Phi4Flash))/phi4flash/checkpoint/mamba/scan/pallas_call"),
        ("fusion.6", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/mamba/scan/transpose"),
        ("fusion.7", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/mamba/gate_out/dot_general"),
        ("fusion.8", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/gmu/dot_general"),
        ("fusion.9", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/diff_attn/proj/dot_general"),
        ("flash_attention_fwd.3", "custom-call", "jvp(Phi4Flash)/phi4flash/checkpoint/diff_attn/flash/pallas_call"),
        ("flash_attention_swa_bwd.1", "custom-call", "transpose(jvp(Phi4Flash))/phi4flash/checkpoint/diff_attn/flash/pallas_call"),
        ("fusion.10", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/diff_attn/combine/sub"),
        ("fusion.11", "fusion", "jvp(Phi4Flash)/phi4flash/checkpoint/mlp/dot_general"),
        ("fusion.12", "fusion", "jvp(Phi4Flash)/phi4flash/head_loss/dot_general"),
        ("fusion.13", "fusion", "jvp(phi4flash/head_loss)/reduce_sum"),
        ("fusion.14", "fusion", "jvp(Phi4Flash)/phi4flash/concatenate")]]
    + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "phi4flash/embed", "fusion.3": "phi4flash/norm",
    "fusion.4": "phi4flash/mamba/proj", "causal_conv1d_fwd.1": "phi4flash/mamba/conv",
    "fusion.5": "phi4flash/mamba/dt", "selective_scan_fwd.2": "phi4flash/mamba/scan",
    "selective_scan_bwd.2": "phi4flash/mamba/scan", "fusion.6": "phi4flash/mamba/scan",
    "fusion.7": "phi4flash/mamba/gate_out", "fusion.8": "phi4flash/gmu",
    "fusion.9": "phi4flash/diff_attn/proj", "flash_attention_fwd.3": "phi4flash/diff_attn/flash",
    "flash_attention_swa_bwd.1": "phi4flash/diff_attn/flash",
    "fusion.10": "phi4flash/diff_attn/combine", "fusion.11": "phi4flash/mlp",
    "fusion.12": "phi4flash/head_loss", "fusion.13": "phi4flash/head_loss",
    "fusion.14": "phi4flash"}
SECONDS = {
    "fusion.1": 0.050, "fusion.2": 0.020, "fusion.3": 0.012, "fusion.4": 0.040,
    "causal_conv1d_fwd.1": 0.010, "fusion.5": 0.014, "selective_scan_fwd.2": 0.010,
    "selective_scan_bwd.2": 0.030, "fusion.6": 0.002, "fusion.7": 0.026, "fusion.8": 0.028,
    "fusion.9": 0.046, "flash_attention_fwd.3": 0.030, "flash_attention_swa_bwd.1": 0.050,
    "fusion.10": 0.006, "fusion.11": 0.400, "fusion.12": 0.040, "fusion.13": 0.008,
    "fusion.14": 0.004, "copy.4": 0.010}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_layers_parts_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 0.836, "window_s": 0.85,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "kernel_s": driver._dense.kernel_seconds(per_op_s, scopes, driver.KERNEL_PREFIXES)}
    return {"trace": trace, "window": {"step_ms": 430.0, "batch": 1, "chips": 1},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"diff_flash_flops_per_step": 3.28e12, "s6_scan_bytes_per_step": 3.027e9,
                      "model_flops_per_sample": 37.53e12}}


@pytest.mark.parametrize("name,want", [
    ("sambay_mamba_ms", 66.0),           # proj 20 + conv 5 + dt 7 + scan 21 + gate_out 13
    ("sambay_scan_ms", 21.0),            # the two kernels 5 + 15 and XLA's transposes 1
    ("sambay_scan_roofline", 100 * (3.027e9 / 819e9) / 0.021),
    ("sambay_gmu_ms", 14.0),
    ("sambay_diff_attn_ms", 66.0),       # proj 23 + flash 15 + 25 + combine 3
    ("sambay_diff_flash_ms", 40.0),      # the windowed kernels' name starts with the full ones'
    ("sambay_diff_flash_roofline", 100 * (3.28e12 / 197e12) / 0.040),
    ("sambay_mlp_ms", 200.0),
    ("head_loss_ms", 34.0),       # head_loss 20 + 4, embed 10
    ("optimizer_ms", 25.0),
    ("lm_mfu_pct", 100 * 37.53e12 / 0.418 / 197e12),
    ("step_ms", 430.0),                  # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 0.836 / 0.85))])
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
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0}, "kernel_s": {}},
                 "shape": {}, "peaks": None, "window": {"batch": 1, "chips": 1}},
                {"trace": {"steps": 2, "scope_s": {"olmoe/attn": 1.0, "olmoe/head_loss": 1.0},
                           "kernel_s": {"mellum/full/attn": {"flash_attention": 0.5}}},
                 "shape": {"attention_flops_per_step": 1.0}, "window": {"batch": 1, "chips": 1},
                 "peaks": None}):
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
    assert resolved["traffic"]["name"] == "resident-lm-sambay-8k"
    assert CELL in [w["name"] for w in bench["workloads"]]     # (no count: later PRs add)
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        code = f.read().split('"""')[2]
    assert "model_zoo" not in code and "elasticdl_tpu" not in code and "pallas" not in code
    for name in ("hyper", "loss_terms", "loss", "adamw_step", "TOLERANCES"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) == {"loss_rel", "mu_rel_l2", "update_rel_l2"}


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) >= {
        "second_map_left_out", "memory_after_the_gate", "window_dropped",
        "head_share_of_the_tied_gradient_dropped", "layernorm_bias_dropped"}
    assert "scan_state_in_bfloat16" in departures.CONTROLS
    assert set(departures.REFERENCE_CONTROLS) == {
        "reference_in_bfloat16", "reference_in_bfloat16_float32_optimizer"}
    assert set(departures.BELOW_THE_NOISE) <= set(departures.DEPARTURES) | set(departures.CONTROLS)


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
    log = proc.stdout
    assert "'memory/reads': 2" in log and "'shared_kv/reads': 4" in log    # 2 steps
