"""The seam the benchmark's precision controls go through still bites: both
routes of the delta rule, in both forms, take Γ from
`delta_rule.cumulative_log_decay`, looked up in the module when a program is
traced — `benchmark/rehearse/departures_kimi_linear.py`
(`cumulative_decay_in_bfloat16`) and `departures_qwen3_next.py`
(`decay_in_bfloat16`) patch it BY NAME. With the function patched as they patch
it, the tiny presets' loss must move: on the plain route at the presets' narrow
heads and on the kernel route (`ops/pallas_delta_rule.py` in interpret mode) at
heads of a whole lane tile, for Kimi Linear's decay a channel and for
Qwen3-Next's one decay a head. A cumulative sum made INSIDE the kernels would
pass this file's kernel cases by the patch and fail them. A file of its own so
that an xdist worker of its own takes its eight traced programs.
"""

import jax
import pytest

from benchmark import common
from elasticdl_tpu.ops import delta_rule, pallas_attention
from tests.test_kimi_linear import lm as kimi
from tests.test_qwen3_next import lm as qwen3

_rounded = common.load_module("rehearse", "departures_glm4_moe_lite")._rounded

# form -> (the model's harness, its linear heads at one lane tile)
FORMS = {
    "channel": (kimi, {"linear_num_heads": 2, "linear_head_dim": 128}),
    "scalar": (qwen3, {"linear_num_key_heads": 1, "linear_num_value_heads": 2,
                       "linear_key_head_dim": 128, "linear_value_head_dim": 128}),
}


def loss_of(lm, more):
    """The first batch's loss from lively parameters, by a program traced
    here and now."""
    spec, trainer = lm.fresh_trainer(**more)
    batch = lm.batches(steps=1)[0]
    params = lm.lively(trainer.init_state(batch)).params
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda p, b: lm.terms(spec, p, b))(params, batch)["loss"])


@pytest.mark.parametrize("route", ["plain", "kernel"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_rounded_cumulative_decay_moves_the_loss(monkeypatch, form, route):
    lm, wide = FORMS[form]
    more = {**(wide if route == "kernel" else {}), **lm.short}
    if route == "kernel":
        # the signal alone: a kernel under `jax.checkpoint` cannot run inside
        # the TPU interpreter's context
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    as_it_is = loss_of(lm, more)
    plain, seen = delta_rule.cumulative_log_decay, []

    def rounding(g):
        seen.append(g.shape)
        return _rounded(plain(g), jax)

    # as `departures_qwen3_next.applied` does: the kernels are `jax.jit`s of
    # their own, and a patch of what they look up is not in their cache's key
    try:
        with monkeypatch.context() as patch:
            patch.setattr(delta_rule, "cumulative_log_decay", rounding)
            jax.clear_caches()
            patched = loss_of(lm, more)
    finally:
        jax.clear_caches()
    # the kernel route hands Γ's function chunks of a (B, T, ·) plane, the
    # plain one a view by head: (B, H, n, L, d), or (B, H_k, r, n, L, 1)
    assert seen and all((len(shape) == 4) == (route == "kernel") for shape in seen), seen
    # the same program twice gives the same bits; float32 resolves 1e-7 here
    assert abs(patched - as_it_is) > 2e-7 * abs(as_it_is), (form, route, as_it_is, patched)
