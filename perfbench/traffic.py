"""The one traffic generator: reads a mix's data file and makes its inputs
from ``--seed``.

Every seed gets the same amount of work: the same multiset of sizes, in
another order, with other task profiles, prompts and fleet values. So two
seeds differ no more than two runs of one seed, and the spread a run
measures is the system's, not the draw's.

Scheduler mixes (``"kind": "closed_batches"``) yield task profiles;
serving mixes (``"kind": "static_batches"``) yield batches of prompts
with their output lengths.
"""
from __future__ import annotations

import itertools
import json
import math
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> Dict:
    path = HERE / "traffic" / f"{name}.json"
    with open(path) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"{path}: 'name' must be {name!r}")
    return spec


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent numpy stream per purpose; any non-negative seed, also
    past 64 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    words = [seed >> (32 * i) & 0xFFFFFFFF
             for i in range(max(1, -(-seed.bit_length() // 32)))]
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words + [0xBE] + tag))


# ---------------------------------------------------------------------------
# Scheduler traffic
# ---------------------------------------------------------------------------


def _profiles(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """(n, 2) distinct (cpu, mem_mb) task profiles."""
    lo_c, hi_c = spec["cpu"]
    lo_m, hi_m = spec["mem_mb"]
    return np.stack([rng.uniform(lo_c, hi_c, n), rng.uniform(lo_m, hi_m, n)],
                    axis=1)


def closed_batches(spec: Dict, seed: int,
                   stream: str = "tasks") -> Iterator[np.ndarray]:
    """Batches of ``batch`` distinct profiles each, endlessly."""
    rng = rng_for(seed, stream)
    while True:
        yield _profiles(spec, rng, spec["batch"])


# ---------------------------------------------------------------------------
# Serving traffic
# ---------------------------------------------------------------------------


def deck(lengths: Dict):
    """Lengths, endlessly: smooth weighted round robin over
    ``lengths["values"]`` by ``lengths["weights"]``, so every prefix of the
    deck follows the weights as closely as whole items allow, in the same
    order for every seed."""
    vals, w = lengths["values"], lengths["weights"]
    cur = [0.0] * len(vals)
    while True:
        cur = [c + wi for c, wi in zip(cur, w)]
        i = max(range(len(vals)), key=lambda k: cur[k])
        cur[i] -= sum(w)
        yield vals[i]


def output_lengths(spec: Dict) -> List[int]:
    """The ``batch`` output lengths of every batch: the first ``batch``
    items of the output deck."""
    return list(itertools.islice(deck(spec["output_len"]), spec["batch"]))


def serve_batches(spec: Dict, seed: int, vocab: int):
    """Batches, endlessly: (prompt length L, (batch, L) int32 prompts of
    uniform token ids, output lengths in slot order). Every batch has the
    same output lengths, shuffled over its slots by the seed."""
    rng = rng_for(seed, "prompts")
    outs = output_lengths(spec)
    for L in deck(spec["prompt_len"]):
        prompts = rng.integers(0, vocab, (spec["batch"], L), dtype=np.int32)
        yield L, prompts, [outs[i] for i in rng.permutation(len(outs))]


def max_context(spec: Dict) -> int:
    return max(spec["prompt_len"]["values"]) + max_output(spec)


def max_output(spec: Dict) -> int:
    return max(spec["output_len"]["values"])


def ceil_to(x: float, m: int) -> int:
    return int(math.ceil(x / m) * m)
