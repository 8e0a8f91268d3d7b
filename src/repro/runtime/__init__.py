"""Run-time services.  ``faults`` loads on first use: it builds on
``repro.core``, which itself records spans through ``runtime.trace``."""

_FAULTS = ("FaultInjector", "ElasticPool")


def __getattr__(name):
    if name in _FAULTS:
        from . import faults
        return getattr(faults, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
