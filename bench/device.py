"""The device a run stands on: the gate that refuses anything but a TPU on
the program's native Pallas path, the table of peaks, and a log of
compilations from JAX's monitoring events."""
from __future__ import annotations

import os
import sys

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class GateError(SystemExit):
    """The run cannot stand for the chip: it exits non-zero, no result."""


def peaks_for(kind: str) -> dict:
    """The peaks of ``kind``; a device missing from the table is an error,
    never a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise GateError(f"bench: no peaks for device_kind {kind!r}; "
                        f"the table has {sorted(PEAKS)}") from None


def device_gate(chips: int) -> dict:
    """The device as JAX reports it; exits unless it is a TPU on the
    native Pallas path with at least ``chips`` devices."""
    import jax
    from repro import kernels
    devs = jax.devices()
    d0 = devs[0]
    print(f"bench: jax {jax.__version__}, platform {d0.platform}, "
          f"device_kind {d0.device_kind!r}, {len(devs)} device(s)",
          file=sys.stderr, flush=True)
    if d0.platform != "tpu":
        raise GateError(f"bench: needs a TPU, JAX found {d0.platform!r}")
    for var in ("REPRO_FLAT_PALLAS", "REPRO_AGG_PATH"):
        if var in os.environ:
            raise GateError(f"bench: {var} is set; unset it to run the "
                            "default path")
    flags = kernels.pallas_flags(None, None)
    if flags != (True, False):
        raise GateError(f"bench: kernels resolve to (use_pallas, interpret)"
                        f"={flags}, not native Pallas")
    if len(devs) < chips:
        raise GateError(f"bench: the cell needs {chips} chip(s), JAX sees "
                        f"{len(devs)}")
    peaks_for(d0.device_kind)
    return describe(chips)


def describe(chips: int) -> dict:
    """platform, kind and count of the devices a run uses."""
    import jax
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the devices used (0 where the
    backend keeps no such statistic)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileLog:
    """Compile seconds and persistent-cache hits/misses, as JAX's
    monitoring events report them.  ``events`` counts every program that
    the backend compiled or the persistent cache handed back: a shape seen
    for the first time in this process."""

    def __init__(self):
        import jax
        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        self.hits += event == CACHE_HIT
        self.misses += event == CACHE_MISS

    @property
    def events(self) -> int:
        return self.compiles + self.hits
