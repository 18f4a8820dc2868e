"""The generators: the same seed gives the same inputs, every seed the
same sizes."""
import numpy as np

import _chipbench
import spec
import traffic


def _llm_traffic():
    cell = spec.resolve(spec.load_benchmark(), "qwen3-g22")
    return cell.traffic, cell.config["vocab_size"]


def test_token_batches_deterministic_per_seed():
    t, vocab = _llm_traffic()
    t = dict(t, seq_len=64)
    a = traffic.token_batches(_chipbench.BIG_SEED, t, vocab)
    b = traffic.token_batches(_chipbench.BIG_SEED, t, vocab)
    c = traffic.token_batches(_chipbench.BIG_SEED + 1, t, vocab)
    assert len(a) == t["distinct_batches"]
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
        assert x["tokens"].shape == z["tokens"].shape
        assert not np.array_equal(x["tokens"], z["tokens"])
    # the batches of one run all differ, labels are tokens shifted by one
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    np.testing.assert_array_equal(a[0]["tokens"][:, 1:], a[0]["labels"][:, :-1])
    assert a[0]["tokens"].max() < vocab and a[0]["tokens"].min() >= 0


def test_sub_seed_takes_large_seeds():
    s = traffic.sub_seed(2 ** 33 + 5, 0)
    assert 0 <= s < 2 ** 31 and s == traffic.sub_seed(2 ** 33 + 5, 0)
    assert s != traffic.sub_seed(2 ** 33 + 5, 1)


def test_action_stream_fixed_for_the_cell():
    cell = spec.resolve(spec.load_benchmark(), "mnist-varfreq")
    m = cell.config["n_edges"]
    a = traffic.action_stream(cell.traffic, m)
    b = traffic.action_stream(cell.traffic, m)
    np.testing.assert_array_equal(a, b)
    lo, hi = cell.traffic["gamma_range"]
    assert a.shape == (cell.traffic["n_actions"], 2 * m)
    assert a.min() >= lo and a.max() <= hi
