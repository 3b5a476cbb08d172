import json

import generate
import harness

#: a corpus mix of every shape
WIDE = {
    "kind": "corpus",
    "parts": [
        {"shape": "wide", "count": 64, "guards": [6, 7, 8]},
        {"shape": "loop", "count": 32, "caps": [31, 63, 127, 255]},
        {"shape": "fixture_mutant", "count": 32},
    ],
}
FRESH = json.loads((harness.BENCH / "traffic" / "fresh.json").read_text())
BIG = 2**31 + 12345


def test_corpus_same_seed_same_bytes():
    assert generate.corpus(WIDE, BIG, 0) == generate.corpus(WIDE, BIG, 0)
    assert len(generate.corpus(WIDE, BIG, 0)) == 128


def test_corpus_other_seed_same_sizes_other_constants():
    a, b = generate.corpus(WIDE, BIG, 0), generate.corpus(WIDE, 7, 0)
    assert a != b
    assert sorted(len(r[0]) for r in a) == sorted(len(r[0]) for r in b)
    # the same shapes in the same places: a seed moves no heavy contract
    assert [r[2].split("#")[0] for r in a] == [r[2].split("#")[0] for r in b]
    assert [len(r[0]) for r in a] == [len(r[0]) for r in b]


def test_interleave_spreads_parts_evenly():
    order = generate._interleave([64, 32, 32])
    assert len(order) == 128 and order.count(0) == 64
    assert all(order[i:i + 4].count(0) == 2 for i in range(0, 128, 4))


def test_stream_of_corpora_never_repeats():
    a, b = generate.corpus(WIDE, BIG, 0), generate.corpus(WIDE, BIG, 1)
    assert not {r[0] for r in a} & {r[0] for r in b}
    warm = generate.corpus(WIDE, BIG, -1)
    assert not {r[0] for r in a} & {r[0] for r in warm}


def test_stream_is_unique_and_round_robin():
    it = generate.stream(FRESH, BIG)
    rows = [next(it) for _ in range(40)]
    assert len({r[0] for r in rows}) == 40
    families = [r[2].split("#")[0] for r in rows[:13]]
    assert len(set(families)) == 13
    again = generate.stream(FRESH, BIG)
    assert [next(again) for _ in range(40)] == rows


def test_fixture_round_follows_the_mix_order():
    order = FRESH["parts"][0]["order"]
    it = generate.stream(FRESH, BIG)
    rows = [next(it) for _ in range(26)]
    families = [r[2].split("#")[0] for r in rows]
    assert families == order + order
    # families the order leaves out follow it, by name
    mix = {"parts": [{"shape": "fixture_mutant", "order": ["suicide.sol"]}]}
    it = generate.stream(mix, BIG)
    names = [next(it)[2].split("#")[0] for _ in range(3)]
    assert names == ["suicide.sol", "calls.sol", "environments.sol"]
