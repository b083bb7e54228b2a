"""The shape functions against counts made by hand at tiny sizes."""

from benchmark import common


def test_deepfm_hand_count():
    flops = common.load_module("flops", "deepfm")
    p = {"embedding_dim": "2", "hidden": "4,3", "field_vocab": "10"}
    # tower input 26*2 + 13 = 65; layers 65x4, 4x3, 3x1 -> 260 + 12 + 3 = 275 MACs
    assert flops.tower_macs(p) == 275
    # + the 13-wide linear unit; 6 FLOPs per MAC forward + backward
    assert flops.model_flops_per_sample(p) == 6 * (275 + 13)
    # weights + biases: 260+4, 12+3, 3+1, linear 13+1, bias 1
    assert flops.dense_parameter_count(p) == 264 + 15 + 4 + 14 + 1
    # one step of 2 samples: 52 ids x 12-byte rows x 9 passes, dense x 7, batch once
    assert flops.step_bytes(p, 2) == 52 * 12 * 9 + 298 * 4 * 7 + 2 * 4 * 41
    assert flops.dense_sweep_bytes(p, 260) == 7 * 260 * 12
    assert flops.placement_bytes(p, 2, 260) == 52 * (12 + 4) + 260 * 12


def test_xdeepfm_hand_count():
    flops = common.load_module("flops", "xdeepfm")
    p = {"embedding_dim": "2", "hidden": "4", "cin_sizes": "3,5", "field_vocab": "10"}
    # CIN: layer 1 3 x (26*26) x 2, layer 2 5 x (3*26) x 2, + output unit 3+5
    cin = 3 * 26 * 26 * 2 + 5 * 3 * 26 * 2 + 8
    assert flops.cin_macs(p) == cin
    tower = 65 * 4 + 4 * 1
    assert flops.model_flops_per_sample(p) == 6 * (tower + 13 + cin)


def test_published_cin_is_133_mflop_a_sample():
    flops = common.load_module("flops", "xdeepfm")
    config = common.load_json("configs", "xdeepfm-criteo.json")
    total = flops.model_flops_per_sample(common.model_params(config))
    assert 133e6 < total < 137e6
