"""The traffic generator's data kinds and client-size distributions."""
import numpy as np
import pytest

import spec
import traffic as tr

BASE = {"population": 40, "samples_per_client": 24, "clients_per_round": 4, "batch_size": 8,
        "local_epochs": 1}
PROTOS = {**BASE, "data": {"noise": 1.0, "client_shift": 0.5}}
TOKENS = {**BASE, "data": {"kind": "tokens", "topic_alpha": 0.3}}
LM = {"seq_len": 12, "vocab": 50}
LOGNORMAL = {"lognormal": {"mean": 3.3, "sigma": 0.6}, "min": 8, "max": 64}


def test_prototypes_is_the_default_kind():
    cfg = {"input_shape": [6], "num_classes": 5}
    named = {**PROTOS, "data": {**PROTOS["data"], "kind": "prototypes"}}
    a, b = tr.Federation(PROTOS, cfg, 9), tr.Federation(named, cfg, 9)
    for i in (0, 7):
        for x, y in zip(a.data(i), b.data(i)):
            np.testing.assert_array_equal(x, y)


def test_an_unknown_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        tr.Federation({**BASE, "data": {"kind": "no_such_kind"}}, LM, 1)
    with pytest.raises(spec.SpecError):
        tr.Federation({**BASE, "data": {"kind": "../lib/traffic"}}, LM, 1)


def test_tokens_are_sequences_of_the_vocabulary():
    fed = tr.Federation(TOKENS, LM, 2**40 + 3)
    assert fed.num_classes == LM["vocab"]
    x, y = fed.data(5)
    assert x.dtype == np.int32 and x.shape == (24, LM["seq_len"])
    assert x.min() >= 0 and x.max() < LM["vocab"]
    np.testing.assert_array_equal(x, y)
    again = tr.Federation(TOKENS, LM, 2**40 + 3).data(5)[0]
    np.testing.assert_array_equal(x, again)
    assert not np.array_equal(x, tr.Federation(TOKENS, LM, 2**40 + 4).data(5)[0])


def _bigrams(x):
    """The joint share of each (token, next token) pair in ``x``."""
    c = np.zeros((LM["vocab"], LM["vocab"]))
    np.add.at(c, (x[:, :-1].ravel(), x[:, 1:].ravel()), 1.0)
    return c / c.sum()


def test_a_topic_makes_the_next_token_predictable():
    """A client of one topic (tiny ``topic_alpha``): the successor seen
    most often after each token in half of its data is the next token in
    the other half far more often than a uniform guess, 1 in ``vocab``."""
    one = {**TOKENS, "samples_per_client": 400, "data": {**TOKENS["data"], "topic_alpha": 0.001}}
    x = tr.Federation(one, LM, 7).data(0)[0]
    seen = _bigrams(x[:200])
    guess = np.argmax(seen, axis=1)
    cur, nxt = x[200:, :-1].ravel(), x[200:, 1:].ravel()
    known = seen.sum(axis=1)[cur] > 0
    assert np.mean(guess[cur[known]] == nxt[known]) > 5.0 / LM["vocab"]


def _guessed(a, b):
    """The share of ``b``'s next tokens that the successor seen most often
    after the current token in ``a`` guesses."""
    guess = np.argmax(_bigrams(a), axis=1)
    return np.mean(guess[b[:, :-1].ravel()] == b[:, 1:].ravel())


@pytest.mark.parametrize("alpha,non_iid", [(0.3, True), (100.0, False)])
def test_topic_alpha_sets_how_far_clients_differ(alpha, non_iid):
    """How well half of a client's data guesses the next tokens of the
    other half of its own, over how well it guesses another client's:
    about 1 where every client mixes the topics alike (large
    ``topic_alpha``), well above 1 where each draws a mix of its own
    (small)."""
    fed = tr.Federation({**TOKENS, "samples_per_client": 400,
                         "data": {**TOKENS["data"], "topic_alpha": alpha}}, LM, 11)
    xs = [fed.data(i)[0] for i in range(8)]
    within = np.mean([_guessed(x[:200], x[200:]) for x in xs])
    between = np.mean([_guessed(xs[i][:200], xs[j][200:])
                       for i in range(8) for j in range(8) if i != j])
    assert (within / between > 1.5) == non_iid


def test_a_number_gives_every_client_that_size():
    sizes = tr.client_sizes(24, 10, seed=3)
    assert sizes.tolist() == [24] * 10


def test_lognormal_sizes_are_one_set_in_a_seeds_order():
    a, b = tr.client_sizes(LOGNORMAL, 200, seed=1), tr.client_sizes(LOGNORMAL, 200, seed=2**35)
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert a.min() >= 8 and a.max() <= 64 and len(set(a.tolist())) > 20
    # the median of the set is the lognormal's median, exp(mean)
    assert abs(np.median(a) - np.exp(3.3)) <= 1.0


def test_federation_reads_its_sizes():
    fed = tr.Federation({**TOKENS, "samples_per_client": LOGNORMAL}, LM, 5)
    want = tr.client_sizes(LOGNORMAL, TOKENS["population"], 5)
    assert [fed.size(i) for i in range(fed.population)] == want.tolist()
    assert fed.max_size() == want.max()
    assert len(fed.data(3)[0]) == want[3]
    assert fed.samples_of([0, 1]) == int(want[0] + want[1])
    assert [len(r) for r in tr.client_schedule(fed, 3, 0)][:1] == [8]
    assert len(tr.client_schedule(fed, 3, 0)) == -(-int(want[3]) // 8)


def test_an_unknown_size_distribution_is_an_error():
    with pytest.raises(spec.SpecError):
        tr.client_sizes({"uniform": [1, 2]}, 10, 1)
