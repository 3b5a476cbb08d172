"""The one traffic generator: it reads a mix's parameters
(benchmark/traffic/<name>.json) and the run's seed, and yields the
bytecode the program receives. The same seed gives the same bytes;
another seed gives the same sizes, shapes and order with other
constants.

A mix is a list of parts, each naming a shape of benchmark/contracts.py
and how many contracts it adds:

- `wide`: `guards` cycles through the guard counts;
- `loop`: `caps` cycles through the loop caps; the assert's 4-byte
  magic is drawn from the seed;
- `fixture_mutant`: the vendored fixtures round-robin by family, each a
  constant mutant drawn from the seed; `order` names the families in
  the order of the round (those it leaves out follow by name).

`corpus(mix, seed, index)` is the index-th corpus of a stream of
corpora that differ only in their constants; `stream(mix, seed)`
yields the parts' contracts one by one, never repeating one.
"""

from __future__ import annotations

import random
from typing import Dict, Iterator, List, Tuple

import contracts

Row = Tuple[str, str, str]  # (runtime hex, creation hex, name)


def _draw(part: Dict, k: int, rng: random.Random, families) -> Tuple[str, str]:
    """(name, runtime hex) of the k-th contract of a part."""
    shape = part["shape"]
    if shape == "wide":
        guards = part["guards"][k % len(part["guards"])]
        return f"wide{guards}", contracts.wide_contract(guards, rng.getrandbits(31))
    if shape == "loop":
        cap = part["caps"][k % len(part["caps"])]
        return f"loop{cap}", contracts.loop_contract(cap, rng.getrandbits(32))
    if shape == "fixture_mutant":
        rank = {name: i for i, name in enumerate(part.get("order", ()))}
        families = sorted(families, key=lambda f: (rank.get(f[0], len(rank)), f[0]))
        family, code = families[k % len(families)]
        mutant = contracts.mutate_constants(bytes.fromhex(code), rng)
        return family, mutant.hex()
    raise ValueError(f"unknown shape {shape!r}")


def _interleave(counts: List[int]) -> List[int]:
    """Part indices in a fixed order that spreads each part evenly
    (smooth weighted round-robin): the same for every seed, so that a
    seed changes the constants and not where the heavy contracts sit."""
    total = sum(counts)
    credit = [0] * len(counts)
    order = []
    for _ in range(total):
        for p, c in enumerate(counts):
            credit[p] += c
        p = max(range(len(counts)), key=lambda i: credit[i])
        credit[p] -= total
        order.append(p)
    return order


def corpus(mix: Dict, seed: int, index: int = 0) -> List[Row]:
    """One corpus of the mix: every part's contracts, interleaved."""
    families = contracts.fixtures()
    parts = mix["parts"]
    drawn = [0] * len(parts)
    rows: List[Row] = []
    for p in _interleave([part["count"] for part in parts]):
        k = drawn[p]
        drawn[p] += 1
        rng = random.Random(f"{seed}:{index}:{p}:{k}")
        name, code = _draw(parts[p], k, rng, families)
        rows.append((code, "", f"{name}#{index}.{p}.{k}"))
    return rows


def stream(mix: Dict, seed: int, tag: str = "window") -> Iterator[Row]:
    """The mix's contracts one after another, parts interleaved, each
    drawn afresh: the k-th contract of a part is new for every k."""
    families = contracts.fixtures()
    k = 0
    while True:
        for p, part in enumerate(mix["parts"]):
            rng = random.Random(f"{seed}:{tag}:{p}:{k}")
            name, code = _draw(part, k, rng, families)
            yield code, "", f"{name}#{tag}.{p}.{k}"
        k += 1
