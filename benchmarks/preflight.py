"""Device preflight for measurement entry points.

A number printed under a device metric's name must come from the device.
So ``benchmarks/runner.py`` and ``benchmarks/replay.py`` call
:func:`require_chip` before anything else, and a run that finds no TPU
FAILS with the probe's error — there is no CPU fallback and no degraded
label. The probe is a plain ``jax.devices()`` in the measuring process
itself: the chip belongs to one process at a time, so a child-process
probe would either be refused the chip or keep the parent from it.
"""

from __future__ import annotations

import time
from typing import Dict


def require_chip() -> Dict:
    """``{"platform", "kind", "count", "latencyS"}`` of the attached TPU;
    raises ``RuntimeError`` (with jax's own error when backend start-up
    failed) when the first device is not a TPU."""
    import jax
    t0 = time.perf_counter()
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise RuntimeError(f"device probe failed: {e}") from e
    probe = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs),
             "latencyS": round(time.perf_counter() - t0, 2)}
    if probe["platform"] != "tpu":
        raise RuntimeError(
            f"device probe found platform {probe['platform']!r} "
            f"({probe['count']} x {probe['kind']}), not a TPU: a "
            "measurement entry point does not fall back to the CPU")
    _publish_probe(probe)
    return probe


def _publish_probe(probe: Dict) -> None:
    """Probe latency + backend into the process metrics registry
    (service/telemetry): scrape surfaces answer "which backend, how far
    away" for the lifetime of the bench process."""
    from spark_rapids_tpu.service.telemetry import MetricsRegistry
    reg = MetricsRegistry.get()
    reg.gauge("tpu_preflight_probe_seconds",
              "jax.devices() probe latency").set(probe["latencyS"])
    reg.gauge("tpu_preflight_backend_info",
              "constant 1; resolved bench backend label",
              backend=probe["platform"]).set(1)
