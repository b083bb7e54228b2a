"""The OLMoE cell's check on the CPU at the tiny preset: the plain reference's
router on a tie (what read `correct: false` at seed 157194244, PR 66), and the
precision control of `rehearse/departures_olmoe.py` — the reference with its
matmuls' operands in float8 in the program's place — which must read
`correct: false` where the program as it is reads true."""

import json

import numpy as np

from benchmark import common

reference = common.load_module("reference", "olmoe")
departures = common.load_module("rehearse", "departures_olmoe")

TINY = ("vocab_size=256;hidden_size=64;num_attention_heads=4;intermediate_size=32;"
        "num_experts=8;num_experts_per_tok=2")


def test_the_reference_router_chooses_exactly_k_where_two_probabilities_are_equal():
    import jax.numpy as jnp

    hp = {"num_experts_per_tok": 2, "rms_norm_eps": 1e-5}
    rng = np.random.default_rng(0)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    w[:, 5] = w[:, 2]                   # experts 2 and 5: the same logit, always
    p = {"ffn_norm": jnp.ones((16,), jnp.float32), "router": jnp.asarray(w)}
    x = jnp.asarray(rng.normal(size=(1, 64, 16)).astype(np.float32))
    _, _, probs, chosen = reference.router(p, x, hp)
    probs, chosen = np.asarray(probs), np.asarray(chosen)
    assert np.all(probs[:, 2] == probs[:, 5])
    assert np.all(chosen.sum(-1) == 2)                  # never three
    second = np.sort(probs, axis=-1)[:, -2]
    tied_for_last = (probs[:, 2] == second) & (np.sum(probs > second[:, None], -1) == 1)
    assert tied_for_last.any()          # the case is drawn: 2 and 5 tie for slot two
    assert np.all(chosen[tied_for_last, 2]) and not np.any(chosen[tied_for_last, 5])
    # and where nothing ties the choice is the k largest
    no_tie = ~((probs[:, 2] >= second) & (probs[:, 5] >= second))
    assert np.all(chosen[no_tie] == (probs[no_tie] >= second[no_tie, None]))


def test_the_float8_control_reads_incorrect_and_the_program_correct(tmp_path):
    out = tmp_path / "readings.jsonl"
    assert departures.main(["--seeds", "5", "--control_seeds", "5", "--model_params", TINY,
                            "--seq_len", "32", "--out", str(out)]) == 0
    rows = {r["run"]: r for r in map(json.loads, out.read_text().splitlines())}
    assert rows["program"]["correct"] and not rows["program"]["failures"]
    control = rows["reference_in_float8"]
    assert not control["correct"]
    assert any(f.startswith("mu_rel_l2.") for f in control["failures"])
    assert control["figures"]["routing_agreement"] < rows["program"]["figures"]["routing_agreement"]
