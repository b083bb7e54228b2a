"""Operations and bytes the xDeepFM step needs, from its shapes alone: the
DeepFM functions plus the Compressed Interaction Network, whose layer k is one
matrix product of O_k outputs over H_k * 26 inputs for each of the D
embedding coordinates (paper, eq. 6). The outer product that feeds it is
element-wise and is not counted."""

from __future__ import annotations

from benchmark import common

_deepfm = common.load_module("flops", "deepfm")

NUM_DENSE, NUM_CAT = _deepfm.NUM_DENSE, _deepfm.NUM_CAT
dense_sweep_bytes = _deepfm.dense_sweep_bytes
placement_bytes = _deepfm.placement_bytes


def cin_macs(model_params: dict) -> int:
    d = int(model_params["embedding_dim"])
    sizes = _deepfm._ints(model_params["cin_sizes"])
    macs, h = 0, NUM_CAT
    for o in sizes:
        macs += o * h * NUM_CAT * d
        h = o
    return macs + sum(sizes)          # + the CIN's output unit


def cin_parameter_count(model_params: dict) -> int:
    sizes = _deepfm._ints(model_params["cin_sizes"])
    count, h = 0, NUM_CAT
    for o in sizes:
        count += o * h * NUM_CAT
        h = o
    return count + sum(sizes) + 1


def model_flops_per_sample(model_params: dict) -> float:
    return 6.0 * (_deepfm.tower_macs(model_params) + NUM_DENSE
                  + cin_macs(model_params))


def step_bytes(model_params: dict, batch: int) -> float:
    return _deepfm.step_bytes(model_params, batch) \
        + cin_parameter_count(model_params) * 4 * 7
