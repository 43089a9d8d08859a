"""What the harness watches while queries run: XLA compilations (counted
by itself, through ``jax.monitoring``), warnings of the fusion logger, and
nodes of an executed plan that hid the device. The last two are the rules
of ``chip_smoke.py`` (``FusionWarnings``, ``plan_faults``), copied."""

import logging
import threading

FUSION_LOGGER = "spark_rapids_tpu.fusion"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Backend compilations and loads from the persistent cache, apart.
    jax reports ``backend_compile_duration`` around both; a load reports a
    ``cache_hits`` event on the same thread just before."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.loads = 0
        self.load_s = 0.0
        self._hit = threading.local()
        self._lock = threading.Lock()

    def install(self):
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def uninstall(self):
        import jax.monitoring
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self._hit.pending = True

    def _on_duration(self, event, seconds, **_):
        if event != _BACKEND_COMPILE:
            return
        hit = getattr(self._hit, "pending", False)
        self._hit.pending = False
        with self._lock:
            if hit:
                self.loads += 1
                self.load_s += seconds
            else:
                self.compiles += 1
                self.compile_s += seconds

    def snapshot(self):
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self.compile_s,
                    "loads": self.loads, "load_s": self.load_s}


def delta(after, before):
    return {k: after[k] - before[k] for k in after}


class FusionWarnings(logging.Handler):
    """Every WARNING+ record of the fusion logger: a fused program that did
    not compile, answered by the per-op eager path instead."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def plan_faults(session):
    """What in the LAST executed plan hid the device: CPU-fallback nodes
    and fused stages that gave up."""
    faults = []
    try:
        session.assert_on_tpu()
    except AssertionError as e:
        faults.append("cpu fallback: " + str(e).splitlines()[0])
    for node in _walk(session.last_plan()):
        if getattr(node, "broken", False) or \
                getattr(node, "_fusion_broken", False):
            faults.append(f"fused program of {node.name} fell back to "
                          "per-op eager")
    return faults
