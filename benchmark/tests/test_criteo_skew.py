import numpy as np

from benchmark import common, criteo_skew


def _cards(name="criteo-kaggle"):
    return common.load_json("cardinalities", name + ".json")["fields"]


def test_same_seed_same_records_whatever_the_threads():
    a = criteo_skew.generate(7, 5000, _cards(), threads=1)
    b = criteo_skew.generate(7, 5000, _cards(), threads=8)
    c = criteo_skew.generate(8, 5000, _cards())
    for key in a:
        assert np.array_equal(a[key], b[key])
    assert not np.array_equal(a["cat"], c["cat"])
    assert a["dense"].dtype == np.float32 and a["cat"].dtype == np.int32
    assert a["dense"].shape == (5000, 13) and a["cat"].shape == (5000, 26)
    assert a["dense"].min() >= 0 and set(np.unique(a["labels"])) <= {0, 1}


def test_each_field_stays_within_its_cardinality_and_is_skewed():
    cards = _cards()
    rec = criteo_skew.generate(3, 200_000, cards)
    for f, card in enumerate(cards):
        values, counts = np.unique(rec["cat"][:, f], return_counts=True)
        assert len(values) <= card
        if card <= 30:                      # every value of a tiny field shows
            assert len(values) == card
        if card >= 1000:                    # the head is hot: a Zipf, not uniform
            assert counts.max() > 20 * 200_000 / card
    assert 0.24 < rec["labels"].mean() < 0.27


def test_ranks_invert_the_bounded_pareto_law():
    u = np.array([0.0, 0.5, 0.999999])
    r = criteo_skew.zipf_ranks(u, 1000, 1.1)
    assert r[0] == 0 and 0 < r[1] < 30 and r[2] <= 999
    assert np.all(criteo_skew.zipf_ranks(np.random.default_rng(0).random(1000), 3, 1.1) < 3)


def test_capped_list_is_the_public_one():
    assert sum(_cards()) == 33_762_577
    assert sum(_cards("criteo-1tb-cap40m")) == 187_767_399
