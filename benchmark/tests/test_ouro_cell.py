"""The Ouro cell's pieces that need no chip: the configuration file against
the catalog's published keys, shape functions against counts made by hand, the
HLO-text scope map with the loop's scopes told apart, the readers of the seven
per-layer metrics on a made-up run, and the rehearsal's line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import common

flops = common.load_module("flops", "ouro")
reference = common.load_module("reference", "ouro")
driver = common.load_module("drivers", "resident_lm_dense")
departures = common.load_module("rehearse", "departures_ouro")

CELL = "ouro-2.6b.resident-4k"
NEW_METRICS = ("ut_loop_ms", "ut_attn_ms", "ut_attn_roofline", "ut_mlp_ms",
               "ut_norm_ms", "ut_exit_ms", "optimizer_ms")
# since PR 66 the head's, the optimizer's and the whole step's readings are named
# for the layer, one reader for every model: `workloads` lists this cell among
# others, and another model's scopes are read as this one's are
FOLDED = ("head_loss_ms", "optimizer_ms", "lm_mfu_pct")
# the catalog row's `config` (architectures.jsonl, Ouro-2.6B)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
REDUCED = {"num_hidden_layers": 8}


def _config():
    return common.load_json("configs", "ouro-2.6b.json")


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
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["parameters"] == 2_667_974_657
    for figure in ("612 438 017", "9.80 GB", "2 667 974 657", "51 388 416"):
        assert figure in config["reduced"]["num_hidden_layers"]
    params = _cut()
    assert params["exit_entropy_coef"] == "0.1" and params["total_ut_steps"] == "4"
    # OLMoE's warm-up in tokens over this deployment's tokens a step
    assert params["warmup_steps"] == str(round(10_485_760_000 / (24 * 4096))) == "106667"
    assert "SIX pipeline stages of 8 layers" in config["deployment"]
    assert "FOUR times" in config["deployment"] and "3.9%" in config["deployment"]
    assert set(config["assumed"]) >= {
        "sandwich_norms", "norm_between_passes", "exit_gate", "exit_entropy_coef",
        "rotary_layout", "no_qk_norm_no_bias", "initialisation", "optimizer", "sequence"}
    assert set(config["changed"]) >= {"recomputation", "exits_one_at_a_time"}
    assert "memory_analysis" in config["changed"]["recomputation"]
    for stated in ("every RMSNorm", "the exit gate", "FLOAT32 sum", "bfloat16 operands"):
        assert stated in config["precision"]
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert config["source"].startswith(entry["source"]) and entry["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-ut-4k.json")
    want = {"seq_len": 4096, "batch_per_chip": 1, "steps_per_dispatch": 4,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 0,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_dense", "rehearse": "tiny-lm-ouro"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    vocab = common.load_json("cardinalities", "ouro-vocab.json")
    assert (vocab["vocab_size"], vocab["zipf_s"], vocab["fields"]) == (49152, 1.0, [49152])
    tiny = common.load_json("rehearse", "tiny-lm-ouro.json")["model_params"]
    assert (tiny["num_hidden_layers"], tiny["total_ut_steps"],
            tiny["num_attention_heads"]) == (2, 3, 4)


def test_parameter_counts_by_hand():
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    rest = 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert (layer, rest) == (51_388_416, 201_330_689)
    assert flops.parameter_count(_cut()) == 8 * layer + rest == 612_438_017
    assert flops.parameter_count({**_cut(), "num_hidden_layers": "48"}) \
        == 48 * layer + rest == 2_667_974_657
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * 612_438_017
    assert 0.57 < 16 * 612_438_017 / 2 ** 30 / 15.75 < 0.59              # 9.13 GiB of state
    # what the issue rules out: twelve layers are 13.1 GB of state
    assert 13.0e9 < 16 * flops.parameter_count({**_cut(), "num_hidden_layers": "12"}) < 13.2e9


def test_a_step_is_56_9_tflop_by_the_model():
    p, t = _cut(), 4096
    matmuls = 6 * (4 * 2048 * 2048 + 3 * 2048 * 5632) * t * 32
    attention = 6 * 2 * 128 * 16 * (t * (t + 1) // 2) * 32
    exits = 6 * 2048 * 49152 * t * 4
    assert flops.attention_flops(p, t) == attention == 6_598_680_379_392
    assert flops.exit_flops(p, t) == exits
    assert flops.model_flops_per_sample(p, t) == matmuls + attention + exits
    assert 56.8e12 < matmuls + attention + exits < 57.0e12
    # the exits' share: 17% here, 3.9% of the whole model's matmuls
    assert 0.17 < exits / (matmuls + attention + exits) < 0.18
    assert 0.038 < 4 * 100_663_296 / (192 * 51_380_224 + 4 * 100_663_296) < 0.040
    shape = flops.shape(p, 1, t)
    assert shape["ut_attention_flops_per_step"] == attention
    assert shape["ut_exit_bytes_per_step"] == 4 * 4 * t * 49152 * 4
    assert shape["parameters"] == 612_438_017 and shape["seq_len"] == t


_OP = 'metadata={op_name="jit(f)/'
HLO = "\n".join(["ENTRY %main {"] + [
    f"  %{name} = f32[8]{{0}} {kind}(%a), {_OP}{path}\"}}" for name, kind, path in [
        ("fusion.1", "fusion", "while/body/closed_call/optimizer/add"),
        ("fusion.2", "fusion", "jvp(Ouro)/ouro/embed/gather"),
        ("fusion.3", "fusion", "jvp(Ouro)/ouro/pass/checkpoint/norm/mul"),
        ("fusion.4", "fusion", "transpose(jvp(Ouro))/ouro/pass/checkpoint/rematted_computation/attn/dot_general"),
        ("flash_attention_fwd.3", "custom-call", "jvp(Ouro)/ouro/pass/checkpoint/attn/pallas_call"),
        ("flash_attention_bwd.3", "custom-call", "transpose(jvp(Ouro))/ouro/pass/checkpoint/attn/pallas_call"),
        ("fusion.5", "fusion", "jvp(Ouro)/ouro/pass/checkpoint/mlp/dot_general"),
        ("fusion.6", "fusion", "jvp(Ouro)/ouro/pass/final_norm/mul"),
        ("fusion.7", "fusion", "jvp(Ouro)/ouro/pass/concatenate"),
        ("fusion.8", "fusion", "jvp(Ouro)/ouro/exit/dot_general"),
        ("fusion.9", "fusion", "transpose(jvp(ouro/exit))/while/body/checkpoint/dot_general"),
        ("fusion.10", "fusion", "jvp(Ouro)/ouro/exit_loss/cumprod"),
        ("fusion.11", "fusion", "jvp(ouro/exit_loss)/mul"),
        ("fusion.12", "fusion", "jvp(Ouro)/ouro/cos")]] + ["  %copy.4 = f32[8]{0} copy(%d)", "}"])
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "ouro/embed", "fusion.3": "ouro/pass/norm",
    "fusion.4": "ouro/pass/attn", "flash_attention_fwd.3": "ouro/pass/attn",
    "flash_attention_bwd.3": "ouro/pass/attn", "fusion.5": "ouro/pass/mlp",
    "fusion.6": "ouro/pass/final_norm", "fusion.7": "ouro/pass", "fusion.8": "ouro/exit",
    "fusion.9": "ouro/exit", "fusion.10": "ouro/exit_loss", "fusion.11": "ouro/exit_loss",
    "fusion.12": "ouro"}
SECONDS = {
    "fusion.1": 0.042, "fusion.2": 0.002, "fusion.3": 0.060, "fusion.4": 0.200,
    "flash_attention_fwd.3": 0.040, "flash_attention_bwd.3": 0.080, "fusion.5": 0.400,
    "fusion.6": 0.004, "fusion.7": 0.002, "fusion.8": 0.100, "fusion.9": 0.080,
    "fusion.10": 0.001, "fusion.11": 0.001, "fusion.12": 0.001, "copy.4": 0.010}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_loop_s_parts_apart(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in SECONDS.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 1.02, "window_s": 1.03,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "kernel_s": driver.kernel_seconds(per_op_s, scopes, driver.KERNEL_PREFIXES)}
    return {"trace": trace, "window": {"step_ms": 512.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"ut_attention_flops_per_step": 6.599e12}}


@pytest.mark.parametrize("name,want", [
    ("ut_loop_ms", 393.0),        # norm 30 + attn 100 + 20 + 40 + mlp 200 + final 2 + own 1
    ("ut_attn_ms", 60.0),
    ("ut_attn_roofline", 100 * (6.599e12 / 197e12) / 0.060),
    ("ut_mlp_ms", 200.0),
    ("ut_norm_ms", 32.0),
    ("ut_exit_ms", 91.0),         # exit 50 + 40, exit_loss 0.5 + 0.5
    ("optimizer_ms", 21.0),
    ("step_ms", 512.0),           # the accepted readers, same run
    ("device_idle_pct", 100 * (1 - 1.02 / 1.03))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.010
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell, and this program in another model's."""
    read = common.load_module("layer_metrics", name).read
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0}, "kernel_s": {}},
                 "shape": {}, "peaks": None},
                {"trace": {"steps": 2, "scope_s": {"olmoe/attn": 1.0, "olmoe/head_loss": 1.0},
                           "kernel_s": {"mellum/full/attn": {"flash_attention": 0.5}}},
                 "shape": {"attention_flops_per_step": 1.0},
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
    # (a superset: a later PR's unlisted metric reads this cell too)
    assert {m["name"] for m in resolved["per_layer"]} >= set(NEW_METRICS) | {
        "step_ms", "device_idle_pct", "setup_state_s", "setup_compile_s",
        "setup_cache_misses"}
    assert {m["name"] for m in resolved["end_to_end"]} == {"samples_per_s_per_chip", "setup_s"}
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"]["name"] == "resident-lm-ut-4k"
    assert CELL in [w["name"] for w in bench["workloads"]]     # (no count: later PRs add)
    assert len(resolved["cell"]["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        code = f.read().split('"""')[2]
    assert "model_zoo" not in code and "elasticdl_tpu" not in code and "pallas" not in code
    assert "lax.scan" not in code                       # no scan over the passes
    for name in ("hyper", "loss_terms", "loss", "adamw_step", "TOLERANCES"):
        assert hasattr(reference, name), name
    assert set(reference.TOLERANCES) >= {
        "loss_rel", "loss_expected_rel", "loss_entropy_rel", "exit_pmf_abs",
        "mu_rel_l2", "update_rel_l2"} | {f"loss_exit_{t}_rel" for t in (1, 2, 3, 4)}


def test_every_departure_the_issue_names_has_a_patch():
    assert len(departures.DEPARTURES) == 5 and len(departures.CONTROLS) == 2
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
    assert "'loop/layer_applications': 12" in log          # 2 layers x 3 passes x 2 steps
