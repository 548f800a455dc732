"""FallbackProvider degradation chains (core/api.py).

Covers the KeyError degradation order through one- and two-level chains
(primary -> fallback -> final), bit-identity of ``intensity_batch``
against the scalar ``intensity`` per name/hour across coverage-aware,
coverage-opaque and failing primaries, interval dispatch per-name
routing, and the all-providers-fail contract (the KeyError propagates —
the engine's requeue/dead-letter machinery owns recovery, the provider
never invents a value).
"""
import numpy as np
import pytest

from repro.core.providers import (FallbackProvider, StaticProvider,
                                  intensity_batch, intensity_interval_batch)


class OpaqueProvider:
    """Coverage-opaque: no ``covers``; raises KeyError for unknown names."""

    def __init__(self, table):
        self.table = dict(table)
        self.calls = 0

    def intensity(self, node, hour=0.0):
        self.calls += 1
        return self.table[node]


class LyingProvider:
    """``covers`` claims everything, ``intensity`` knows only ``table`` —
    the optimistic-covers degradation path."""

    def __init__(self, table):
        self.table = dict(table)

    def covers(self, node):
        return True

    def intensity(self, node, hour=0.0):
        return self.table[node]


def test_scalar_degradation_order():
    chain = FallbackProvider(StaticProvider({"a": 1.0}),
                             StaticProvider({"a": 10.0, "b": 20.0}))
    assert chain.intensity("a") == 1.0       # primary wins when covered
    assert chain.intensity("b") == 20.0      # uncovered -> fallback
    with pytest.raises(KeyError):
        chain.intensity("c")                 # nobody covers -> propagate


def test_two_level_chain_resolves_in_order():
    chain = FallbackProvider(
        StaticProvider({"a": 1.0}),
        FallbackProvider(StaticProvider({"b": 2.0}),
                         StaticProvider({"c": 3.0})))
    assert [chain.intensity(n) for n in "abc"] == [1.0, 2.0, 3.0]
    with pytest.raises(KeyError):
        chain.intensity("d")


@pytest.mark.parametrize("primary_cls", [StaticProvider, OpaqueProvider,
                                         LyingProvider])
def test_batch_is_bit_identical_to_scalar(primary_cls):
    primary = (StaticProvider({"a": 111.0, "c": 333.0})
               if primary_cls is StaticProvider
               else primary_cls({"a": 111.0, "c": 333.0}))
    chain = FallbackProvider(primary,
                             StaticProvider({"a": 1.0, "b": 222.0,
                                             "d": 444.0}))
    names = ["a", "b", "c", "d", "a"]
    for hours in (0.0, 7.5):
        batch = np.asarray(intensity_batch(chain, names, hours))
        scalar = np.asarray([chain.intensity(n, hours) for n in names])
        np.testing.assert_array_equal(batch, scalar)
    # array hours: (H, N), each row == the scalar read at that hour
    hs = np.array([0.0, 1.0, 2.0])
    out = np.asarray(intensity_batch(chain, names, hs))
    assert out.shape == (3, 5)
    for i, h in enumerate(hs):
        np.testing.assert_array_equal(
            out[i], [chain.intensity(n, float(h)) for n in names])


def test_batch_all_providers_fail_raises():
    chain = FallbackProvider(StaticProvider({"a": 1.0}),
                             StaticProvider({"b": 2.0}))
    with pytest.raises(KeyError):
        intensity_batch(chain, ["a", "zzz"], 0.0)
    with pytest.raises(KeyError):
        chain.intensity_batch(["zzz"], np.array([0.0, 1.0]))


def test_interval_routes_per_name():
    chain = FallbackProvider(StaticProvider({"a": 100.0}),
                             StaticProvider({"b": 200.0}))
    lo, hi = intensity_interval_batch(chain, ["a", "b"], 0.0)
    # plain providers degrade to zero-width intervals at the point value
    np.testing.assert_array_equal(lo, [100.0, 200.0])
    np.testing.assert_array_equal(hi, [100.0, 200.0])
    with pytest.raises(KeyError):
        intensity_interval_batch(chain, ["a", "zzz"], 0.0)


def test_lying_covers_degrades_not_crashes():
    """An optimistic ``covers`` that later KeyErrors must degrade to the
    per-name path and still produce fallback values, identically to the
    scalar chain."""
    chain = FallbackProvider(LyingProvider({"a": 5.0}),
                             StaticProvider({"a": 50.0, "b": 60.0}))
    out = np.asarray(intensity_batch(chain, ["a", "b"], 0.0))
    np.testing.assert_array_equal(out, [5.0, 60.0])


def test_resilient_wrapper_composes_with_chain():
    """ResilientProvider around a chain: healthy reads delegate
    bit-identically; a blackout serves last-known-good for every name the
    chain had resolved, whichever level resolved it."""
    from repro.resilience import ResilientProvider
    chain = FallbackProvider(StaticProvider({"a": 1.0}),
                             StaticProvider({"b": 2.0}))
    prov = ResilientProvider(chain)
    np.testing.assert_array_equal(prov.intensity_batch(["a", "b"], 0.0),
                                  intensity_batch(chain, ["a", "b"], 0.0))
    prov.begin_blackout()
    np.testing.assert_array_equal(prov.intensity_batch(["a", "b"], 3.0),
                                  [1.0, 2.0])
    with pytest.raises(KeyError):
        prov.intensity("never-seen", 3.0)
