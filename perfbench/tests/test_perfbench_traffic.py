"""The traffic generator: deterministic per seed, different across seeds,
and the same amount of work for every seed."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import itertools  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from perfbench import traffic  # noqa: E402

BIG = 2 ** 31 + 12345
DATA = ROOT / "perfbench" / "tests" / "data"


def _batches(spec, seed, n):
    return list(itertools.islice(traffic.serve_batches(spec, seed, 151936), n))


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_serve_batches_seeded(name):
    spec = traffic.load(name)
    a, b, c = _batches(spec, 7, 6), _batches(spec, 7, 6), _batches(spec, BIG, 6)
    for (la, pa, oa), (lb, pb, ob), (lc, pc, oc) in zip(a, b, c):
        assert la == lb == lc               # same deck for every seed
        assert np.array_equal(pa, pb) and oa == ob
        assert not np.array_equal(pa, pc)
        assert sorted(oa) == sorted(oc)     # same output lengths, reordered
        assert pa.shape == (spec["batch"], la) and pa.dtype == np.int32
    if len(set(a[0][2])) > 1:               # the seed orders the slots
        assert any(x[2] != y[2] for x, y in zip(a, c))


def test_prompt_deck_follows_weights():
    lengths = {"values": [512, 1024, 2048], "weights": [0.35, 0.40, 0.25]}
    deck = list(itertools.islice(traffic.deck(lengths), 20))
    counts = {v: deck.count(v) for v in lengths["values"]}
    assert counts == {512: 7, 1024: 8, 2048: 5}
    assert set(deck[:3]) == {512, 1024, 2048}


def test_output_lengths_follow_deck():
    spec = json.loads((DATA / "tiny-chat.json").read_text())
    assert sorted(traffic.output_lengths(spec)) == [9, 17]
    assert traffic.output_lengths(traffic.load("chat")) == [129] * 8
    assert traffic.output_lengths(traffic.load("longprompt")) == [13] * 8


@pytest.mark.parametrize("name,prompt,output", [("chat", 1020, 129),
                                                ("longprompt", 1500, 13)])
def test_serving_mix_is_the_cited_median(name, prompt, output):
    """Each serving mix is its trace's published median, the prompt on the
    prefill kernel's 128-token block."""
    spec = traffic.load(name)
    assert "arXiv:2311.18677" in spec["source"]
    assert f"median prompt {prompt} tokens" in spec["source"]
    assert f"median output {output} tokens" in spec["source"]
    (L,) = spec["prompt_len"]["values"]
    assert L % 128 == 0 and abs(L - prompt) < 128
    assert spec["output_len"]["values"] == [output]


def test_fleet_regions_same_for_every_seed():
    from perfbench.paths.scheduler import make_fleet

    fleet = json.loads((ROOT / "perfbench" / "configs" /
                        "metro-10k.json").read_text())["fleet"]
    a, b, c = make_fleet(fleet, 3), make_fleet(fleet, 3), make_fleet(fleet, BIG)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["intensity"], c["intensity"])
    want = {r["intensity_g_per_kwh"] for r in fleet["regions"]}
    for f in (a, c):
        vals, counts = np.unique(f["intensity"], return_counts=True)
        assert set(vals.tolist()) == want
        assert counts.max() - counts.min() <= 1


def test_placement_discriminates():
    """On metro-10k's fleet under the distinct traffic, tasks' best nodes
    differ (the memory filter moves them), and Eq. 3 scored in bfloat16
    misplaces tasks where float32 does not: the placement check can see a
    lower precision."""
    import ml_dtypes

    from perfbench.paths.scheduler import make_fleet
    from perfbench.reference import eq3

    cfg = json.loads((ROOT / "perfbench" / "configs" /
                      "metro-10k.json").read_text())
    f = make_fleet(cfg["fleet"], BIG)
    prof = next(traffic.closed_batches(traffic.load("distinct"), BIG))[:256]
    w = np.array([cfg["weights"][k] for k in ("w_r", "w_l", "w_p", "w_b",
                                               "w_c")])
    kw = dict(latency_threshold_ms=cfg["latency_threshold_ms"],
              load_threshold=cfg["load_threshold"])
    tc, tm = prof[:, 0], prof[:, 1]
    best, val = eq3.place(f, tc, tm, w, **kw)
    assert (best >= 0).all() and len(set(best.tolist())) > 1

    def gap(dtype):
        nodes, _ = eq3.place(f, tc, tm, w, dtype=dtype, **kw)
        return float(np.max(val - eq3.score_of(f, tc, tm, nodes, w, **kw)))

    lim = cfg["limits"]["placement_gap"]
    assert gap(np.float32) <= lim < gap(ml_dtypes.bfloat16)


def test_closed_batches_distinct_profiles():
    spec = traffic.load("distinct")

    def first(seed, n=2):
        return list(itertools.islice(traffic.closed_batches(spec, seed), n))

    a = first(3)
    assert np.array_equal(a[0], first(3, 1)[0])
    assert not np.array_equal(a[0], first(4, 1)[0])
    allp = np.concatenate(a)
    assert len(np.unique(allp, axis=0)) == len(allp) == 2 * spec["batch"]
    assert allp[:, 0].min() >= spec["cpu"][0]
    assert allp[:, 1].max() <= spec["mem_mb"][1]


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        traffic.rng_for(-1, "x")
