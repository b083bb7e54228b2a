"""The Keye-VL-2.0 cell's pieces that need no chip: the configuration file
against the catalog's published keys, shape functions against counts made by
hand (the selected pairs against a brute-force count), the HLO-text scope map
with the score plane's blocks told from what makes them, the readers of the
per-layer metrics on a made-up run, the check's way with the selections, and a
CPU rehearsal of the whole cell at the tiny preset."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common

flops = common.load_module("flops", "keye_vl2")
reference = common.load_module("reference", "keye_vl2")
driver = common.load_module("drivers", "resident_lm_dsa")

CELL = "keye-vl-2.0-30b-a3b.resident-16k"
NEW_METRICS = ("dsa_ms", "dsa_index_ms", "dsa_select_ms", "dsa_attn_ms", "dsa_attn_roofline",
               "dsa_index_loss_ms", "held16of128_moe_ms")
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")


def _config():
    return common.load_json("configs", "keye-vl-2.0-30b-a3b.json")


def _cut():
    return common.model_params(_config())


def _uncut():
    return dict(_cut(), vocab_size="151936", num_hidden_layers="48", num_experts="128")


def test_configuration_file_keeps_every_published_key():
    row, config = _catalog(), _config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    params = _cut()
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts", "num_experts_per_tok", "moe_intermediate_size", "rms_norm_eps",
                "vocab_size", "num_hidden_layers", "rope_theta"):
        assert float(params[key]) == float(config[key]), key
    sa = config["sa_config"]
    assert [int(params[k]) for k in ("indexer_num_heads", "indexer_head_dim", "index_topk")] \
        == [sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]] == [16, 64, 2048]
    assert params["router_experts"] == str(config["num_local_experts"]) == "128"


def test_configuration_file_states_the_cut():
    config = _config()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936,
        "parameters": 30_640_656_384, "active_parameters": 3_461_566_464}
    params = _cut()
    assert int(params["first_expert"]) % 16 == 0
    assert params["warmup_steps"] == str(10_485_760_000 // (8 * 16384)) == "80000"
    assert float(params["residual_initializer_range"]) == pytest.approx(0.02 / 96 ** 0.5, rel=1e-6)
    assert "8 chips share each layer" in config["deployment"]
    assert "twelve pipeline stages" in config["deployment"] and "96 chips" in config["deployment"]
    for key in ("qk_norm", "indexer", "chunks", "selection", "index_loss", "aux_loss", "mrope",
                "optimizer", "init", "sequence", "held_share"):
        assert key in config["assumed"], key
    assert "precision" in config and "changed" in config
    with open(common.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200


def test_traffic_file_holds_the_issue_s_parameters():
    traffic = common.load_json("traffic", "resident-lm-dsa-16k.json")
    want = {"seq_len": 16384, "batch_per_chip": 1, "steps_per_dispatch": 2,
            "distinct_stacks": 8, "check_steps": 2, "settle_router_steps": 0,
            "trace_dispatches": 2, "zipf_s": 1.0, "generator": "zipf-tokens",
            "driver": "resident_lm_dsa", "rehearse": "tiny-lm-keye"}
    assert {k: traffic[k] for k in want} == want
    assert traffic["loop"].startswith("closed") and traffic["packing"].startswith("none")
    assert common.load_json("cardinalities", "keye-vocab-slice.json")["vocab_size"] == 18992
    tiny = common.load_json("rehearse", "tiny-lm-keye.json")
    assert tiny["model_params"]["index_topk"] < tiny["traffic"]["seq_len"]
    assert (tiny["model_params"]["num_experts"], tiny["model_params"]["router_experts"]) == (4, 16)


def test_parameter_counts_by_hand():
    attention = 2048 * (4096 + 2 * 512) + 4096 * 2048
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128
    expert = 3 * 2048 * 768
    rest = attention + 256 + indexer + 2 * 2048 + 2048 * 128
    assert (attention, indexer, expert) == (18_874_368, 2_261_120, 4_718_592)
    assert rest + 16 * expert == 96_899_456 and rest + 128 * expert == 625_381_760
    cut = 4 * (rest + 16 * expert) + 2 * 18992 * 2048 + 2048
    assert flops.parameter_count(_cut()) == cut == 465_391_104
    uncut = 48 * (rest + 128 * expert) + 2 * 151936 * 2048 + 2048
    assert flops.parameter_count(_uncut()) == uncut == 30_640_656_384
    assert flops.active_parameter_count(_uncut()) == 3_461_566_464
    assert flops.optimizer_bytes(_cut()) == 7 * 4 * cut
    assert 16 * cut / 2 ** 30 > 0.25 * 15.75                  # the driver's floor
    assert round(16 * cut / 2 ** 30, 2) == 6.93


@pytest.mark.parametrize("seq_len,topk", [
    (1, 1), (7, 1), (7, 3), (16, 16), (16, 17), (37, 8), (64, 5), (200, 64), (130, 4096)])
def test_selected_pairs_against_a_brute_force_count(seq_len, topk):
    assert flops.selected_pairs(seq_len, topk) == sum(min(t + 1, topk) for t in range(seq_len))
    assert flops.causal_pairs(seq_len) == sum(t + 1 for t in range(seq_len))


def test_a_query_keeps_23_percent_of_its_causal_pairs_at_16k():
    p, t = _cut(), 16384
    assert flops.selected_pairs(t, 2048) == 31_458_304
    assert flops.causal_pairs(t) == 134_225_920
    assert round(100 * 31_458_304 / 134_225_920, 1) == 23.4
    assert round(100 * flops.selected_pairs(8192, 2048) / flops.causal_pairs(8192), 1) == 43.7
    assert round(100 * flops.selected_pairs(4096, 2048) / flops.causal_pairs(4096), 1) == 75.0
    attn = flops.attention_flops(p, t)
    assert attn == 6.0 * 2 * 128 * 32 * 4 * 31_458_304
    assert flops.index_score_flops(p, t) == 6.0 * 16 * 64 * 4 * 134_225_920
    assert flops.index_target_flops(p, t) == 2.0 * 128 * 32 * 4 * 31_458_304
    assert flops.expected_held_pairs(p, t) == 16384          # 1024 a held expert
    held = 6 * 4 * 16384 * 3 * 2048 * 768
    every_token = 4 * (18_874_368 + 2_260_992 + 2048 * 128) + 2048 * 18992
    total = flops.model_flops_per_sample(p, t)
    assert total == 6 * every_token * t + held + attn + flops.index_score_flops(p, t) \
        + flops.index_target_flops(p, t)
    shape = flops.shape(p, 1, t)
    assert shape["dsa_attention_flops_per_step"] == attn
    assert shape["held_expert_matmul_flops_per_step"] == held
    assert shape["selected_pairs_per_head"] == 31_458_304
    assert shape["parameters"] == 465_391_104 and shape["seq_len"] == t
    assert flops.shape(p, 1, t, 1000.0)["held_expert_matmul_flops_per_step"] \
        == 6 * 1000 * 3 * 2048 * 768


HLO = '''
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/closed_call/optimizer/add"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/qkv/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Keye))/keye/checkpoint/rematted_computation/attn/rope/mul"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/index/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/select/while/body/scores/dot_general"}
  %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/select/while/body/while/body/reduce_sum"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/index_loss/while/body/scores/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Keye))/keye/checkpoint/attn/index_loss/while/body/exp"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/mul"}
  %fusion.10 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/out/dot_general"}
  %flash_attention_sel_fwd.3 = bf16[1,32,16384,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/attn/pallas_call"}
  %flash_attention_sel_bwd.3 = bf16[1,4,16384,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(Keye))/keye/checkpoint/attn/attn/pallas_call"}
  %fusion.20 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/attn/attn/transpose"}
  %fusion.11 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(Keye))/keye/checkpoint/moe/dispatch/gather"}
  %fusion.12 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/moe/router/dot_general"}
  %grouped_matmul.2 = bf16[32768,768]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(Keye)/keye/checkpoint/moe/while/body/experts/pallas_call"}
  %fusion.13 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/transpose(jvp(keye/head_loss))/mul"}
  %fusion.15 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/embed/gather"}
  %fusion.16 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(Keye)/keye/cos"}
  %copy.4 = f32[8]{0} copy(%d)
}
'''
SCOPES_OF_HLO = {
    "fusion.1": "optimizer", "fusion.2": "keye/attn/qkv", "fusion.3": "keye/attn/rope",
    "fusion.4": "keye/attn/index", "fusion.5": "keye/attn/select/scores",
    "fusion.6": "keye/attn/select", "fusion.7": "keye/attn/index_loss/scores",
    "fusion.8": "keye/attn/index_loss", "fusion.9": "keye/attn", "fusion.10": "keye/attn/out",
    "flash_attention_sel_fwd.3": "keye/attn/attn", "flash_attention_sel_bwd.3": "keye/attn/attn",
    "fusion.20": "keye/attn/attn", "fusion.11": "keye/moe/dispatch",
    "fusion.12": "keye/moe/router", "grouped_matmul.2": "keye/moe/experts",
    "fusion.13": "keye/head_loss", "fusion.15": "keye/embed", "fusion.16": "keye"}


@pytest.mark.parametrize("instruction", sorted(SCOPES_OF_HLO))
def test_scope_map_tells_the_score_blocks_from_what_makes_them(instruction):
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    assert set(scopes) == set(SCOPES_OF_HLO)
    assert scopes[instruction] == SCOPES_OF_HLO[instruction]


def _run():
    seconds = {"fusion.1": 0.050, "fusion.2": 0.040, "fusion.3": 0.020, "fusion.4": 0.010,
               "fusion.5": 0.060, "fusion.6": 0.080, "fusion.7": 0.120, "fusion.8": 0.300,
               "fusion.9": 0.002, "fusion.10": 0.004, "flash_attention_sel_fwd.3": 0.100,
               "flash_attention_sel_bwd.3": 0.300, "fusion.20": 0.006, "fusion.11": 0.006,
               "fusion.12": 0.004, "grouped_matmul.2": 0.030, "fusion.13": 0.034,
               "fusion.15": 0.001, "fusion.16": 0.001, "copy.4": 0.002}
    per_op_s = {f"%{name} = f32[8]{{0}} fusion(%a)": s for name, s in seconds.items()}
    scopes = driver._share.scope_map(HLO, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    trace = {"steps": 2, "busy_s": 1.16, "window_s": 1.17,
             "scope_s": driver._lm.seconds_by_scope(per_op_s, scopes),
             "flash_attention_s": driver._lm.seconds_by_kernel(per_op_s, "flash_attention"),
             "kernel_s": driver._st.kernel_seconds(per_op_s, scopes, driver.KERNEL_PREFIXES)}
    return {"trace": trace, "window": {"step_ms": 585.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "shape": {"dsa_attention_flops_per_step": 6.18e12}}


def test_kernel_seconds_are_kept_under_the_masked_kernels_own_prefix():
    assert _run()["trace"]["kernel_s"] == {
        "keye/attn/attn": {"flash_attention_sel": pytest.approx(0.400)}}
    assert driver._st.KERNEL_PREFIXES == driver.KERNEL_PREFIXES


@pytest.mark.parametrize("name,want", [
    ("dsa_ms", 521.0),            # everything under keye/attn
    ("dsa_index_ms", 95.0),       # the projections 5 + the score blocks 30 + 60
    ("dsa_select_ms", 40.0),      # the search, without the blocks it ranks
    ("dsa_index_loss_ms", 150.0), # target, KL and pull-back, without the blocks
    ("dsa_attn_ms", 200.0),       # the masked kernels alone, by name
    ("dsa_attn_roofline", 100 * (6.18e12 / 197e12) / 0.200),
    ("held16of128_moe_ms", 20.0),
    ("step_ms", 585.0),
    ("device_idle_pct", 100 * (1 - 1.16 / 1.17))])
def test_layer_metric_reader(name, want):
    run = _run()
    assert run["trace"]["scope_s"]["unattributed"] == 0.002
    assert abs(common.load_module("layer_metrics", name).read(run) - want) < 1e-6


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_such_scopes(name):
    """What the parent gives in any cell, and this program in a cell of
    another model."""
    read = common.load_module("layer_metrics", name).read
    mellum_like = {"trace": {"steps": 2, "scope_s": {"mellum/full/attn": 1.0},
                             "kernel_s": {"mellum/full/attn": {"flash_attention": 0.5}},
                             "flash_attention_s": 0.5},
                   "shape": {"global_attention_flops_per_step": 1.0},
                   "peaks": {"bf16_flops_per_s": 1.0}}
    for run in ({"trace": None}, {"trace": {"steps": 2, "busy_s": 1.0, "window_s": 1.0}},
                {"trace": {"steps": 2, "scope_s": {"unattributed": 1.0}, "kernel_s": {},
                           "flash_attention_s": 0.0}, "shape": {}, "peaks": None},
                mellum_like):
        assert read(run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_per_layer_entry_is_bound_to_the_cell(name):
    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "samples_per_s_per_chip" and entry["source"] == "device_trace"
    resolved = common.resolve_cell(CELL)
    assert {m["name"] for m in resolved["per_layer"]} == set(NEW_METRICS) | {
        "step_ms", "device_idle_pct"}
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"]["name"] == "resident-lm-dsa-16k"
    assert len(resolved["cell"]["why"]) <= 200
    # appended after the accepted entries (a later PR appends after these)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index("mellum2-12b-a2.5b.resident-16k")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_selection_figures_on_a_plane_by_hand():
    """Four queries, two keys a query. The program's plane is off by 0.01 in
    one score; its selection differs from the reference's in row 3, where keys
    1 and 2 score 0.50 and 0.505 — inside twice the row's error — and counts
    as outside it where the scores have no error to explain it."""
    import jax.numpy as jnp

    ours = np.array([[[0.9, 0, 0, 0], [0.2, 0.8, 0, 0], [0.1, 0.7, 0.3, 0],
                      [0.9, 0.50, 0.505, 0.1]]], np.float32)
    scores = ours.copy()
    scores[0, 3, 1] = 0.51
    own = reference.own_selection(jnp.asarray(ours), 2)
    kept = reference.own_selection(jnp.asarray(scores), 2)
    figures = {k: float(v) for k, v in driver.selection_figures(
        jnp.asarray(scores), jnp.asarray(ours), kept, own, 2).items()}
    assert figures["pairs_program"] == figures["pairs_reference"] == 1 + 2 + 2 + 2
    assert figures["agreement_min"] == 0.5 and figures["agreement_mean"] == 0.875
    assert figures["outside_error"] == 0
    assert figures["score_rel"] == pytest.approx(0.01 / np.sqrt(np.sum(np.tril(ours[0]) ** 2)),
                                                 rel=1e-4)
    # the same wrong selection from scores WITHOUT an error: nothing explains it
    figures = driver.selection_figures(jnp.asarray(ours), jnp.asarray(ours), kept, own, 2)
    # key 1 of row 3 (key 2, the other that changed sides, IS the threshold)
    assert float(figures["outside_error"]) == 1
    assert float(figures["score_rel"]) == 0.0


def test_read_program_takes_the_step_s_own_three_terms():
    check = driver.DsaStepCheck.__new__(driver.DsaStepCheck)

    class State:
        params = {}

        class opt_state:
            mu, nu = {}, {}

    metrics = [{"loss": np.array([10.34]), "loss_ce": np.array([10.1]),
                "loss_balance": np.array([0.04]), "loss_index": np.array([0.2])}]
    check.read_program(State, metrics, [], ["selections"])
    assert sorted(check.got["terms"]) == ["loss_balance", "loss_ce", "loss_index"]
    assert check.got["selections"] == ["selections"]
    for name in check.got["terms"]:
        assert f"{name}_rel" in reference.TOLERANCES


def test_the_reference_imports_nothing_of_the_program():
    for name in ("keye_vl2", "mellum"):
        with open(os.path.join(common.BENCH_DIR, "reference", name + ".py")) as f:
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
    for counter in ("dsa/tie_rows", "dsa/selected_pairs", "dsa/causal_pairs", "dsa/live_blocks",
                    "dsa/causal_blocks", "router_state/pairs_held_share"):
        assert counter in out.stdout, counter
    assert "selection_agreement" in out.stdout and "index_score_rel" in out.stdout
