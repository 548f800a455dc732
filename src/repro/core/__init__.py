# The paper's primary contribution — implement the SYSTEM here
# (scheduler, optimizer, data path, serving loop, etc.) in the
# host framework. Add sibling subpackages for substrates.
#
# Public API (DESIGN.md): providers, policies and the engine, lazily
# re-exported so `import repro.core` stays cheap.

_API = {
    "CarbonEdgeEngine": "repro.core.api",
    "CarbonIntensityProvider": "repro.core.providers",
    "SchedulingPolicy": "repro.core.api",
    "StaticProvider": "repro.core.providers",
    "TraceProvider": "repro.core.providers",
    "ForecastProvider": "repro.core.providers",
    "FallbackProvider": "repro.core.providers",
    "intensity_batch": "repro.core.providers",
    "WeightedScoringPolicy": "repro.core.policy",
    "VectorizedPolicy": "repro.core.policy",
    "TemporalPolicy": "repro.core.policy",
    "featurize": "repro.core.policy",
    "featurize_cached": "repro.core.policy",
    "FeatureCache": "repro.core.featcache",
}

__all__ = sorted(_API)


def __getattr__(name):
    if name in _API:
        import importlib

        return getattr(importlib.import_module(_API[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
