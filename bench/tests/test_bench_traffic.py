"""The traffic generator: reproducible per seed, same sizes across seeds."""
import json
import os
import sys

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib.traffic import Mix, seed_words

DATA = os.path.join(os.path.dirname(__file__), "data")
TRAFFIC = json.load(open(os.path.join(DATA, "traffic", "tiny.json")))


def _requests(seed, rounds=4):
    mix = Mix(TRAFFIC, seed, vocab_size=512)
    return [mix.request(c, j) for j in range(rounds)
            for c in range(mix.clients)]


def test_same_seed_same_requests():
    big = 2 ** 31 + 12345
    assert _requests(big) == _requests(big)


def test_other_seed_same_sizes_other_tokens():
    a, b = _requests(7), _requests(8)
    sizes = lambda rs, j: sorted((len(r.prompt), r.max_new) for r in rs
                                 if r.index == j)
    for j in range(4):
        assert sizes(a, j) == sizes(b, j)
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_fill_round_has_one_order_for_every_seed():
    """Round 0 (set-up's fill) deals its sizes in the drawn order whatever
    the seed; later rounds in the seed's order."""
    order = lambda seed, j: [(len(r.prompt), r.max_new)
                             for r in _requests(seed) if r.index == j]
    seeds = (7, 8, 2 ** 31 + 12345)
    assert len({tuple(order(s, 0)) for s in seeds}) == 1
    assert any(order(7, j) != order(8, j) for j in (1, 2, 3))


def test_lengths_within_bounds():
    for r in _requests(3, rounds=20):
        assert TRAFFIC["prompt_len"]["min"] <= len(r.prompt) <= \
            TRAFFIC["prompt_len"]["max"]
        assert TRAFFIC["output_len"]["min"] <= r.max_new <= \
            TRAFFIC["output_len"]["max"]
        assert all(0 <= t < 512 for t in r.prompt)


def test_seed_words_cover_large_seeds():
    assert seed_words(0) == [0]
    assert seed_words(2 ** 32 + 5) == [5, 1]
