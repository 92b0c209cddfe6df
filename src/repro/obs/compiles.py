"""A process-wide count of JAX's lowerings and backend compiles.

JAX reports every lowering of a program to MLIR and every backend compile
(a persistent-cache load included) through `jax.monitoring` duration
events.  `install()` registers one listener for them, once per process;
from then on `counts()` holds the totals whether or not an `EngineObs` is
attached, and every live `EngineObs` gets each backend compile in its
``fl_compiles_total`` / ``fl_compile_seconds_total`` counters (label
``fn``: the program's name).  A compile that JAX makes implicitly -- an
eager op's first call, a retrace, an evaluation's first shape -- counts
like an explicit one.

Each lowering also writes a ``fl.lowering`` annotation on the profiler's
host plane, so a trace shows when the process lowered a program: a
benchmark counts them inside its measured window, where there should be
none.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict

from .spans import ANNOTATION_PREFIX, annotate

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOWERING_ANNOTATION = ANNOTATION_PREFIX + "lowering"


class CompileCounter:
    """Totals of one process, and the `EngineObs` bundles it feeds."""

    def __init__(self):
        self.lowerings = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.installed = False
        self._subscribers = weakref.WeakSet()
        self._lock = threading.Lock()   # JAX may compile on any thread

    def subscribe(self, obs) -> None:
        """Feed ``obs``'s compile counters from now on (held weakly)."""
        self._subscribers.add(obs)

    def __call__(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == LOWERING_EVENT:
            with annotate(LOWERING_ANNOTATION):
                pass
            with self._lock:
                self.lowerings += 1
        elif event == BACKEND_COMPILE_EVENT:
            fn = str(kwargs.get("fun_name", ""))
            with self._lock:
                self.compiles += 1
                self.compile_s += duration_secs
                for obs in list(self._subscribers):
                    obs.m_compiles.inc(1, fn=fn)
                    obs.m_compile_s.inc(float(duration_secs), fn=fn)

    def counts(self) -> Dict[str, float]:
        with self._lock:
            return {"lowerings": self.lowerings, "compiles": self.compiles,
                    "compile_s": self.compile_s}


COUNTER = CompileCounter()


def install() -> bool:
    """Register `COUNTER` with `jax.monitoring` (idempotent).  False
    where jax is missing: there is nothing to count."""
    if not COUNTER.installed:
        try:
            from jax import monitoring
        except ImportError:
            return False
        monitoring.register_event_duration_secs_listener(COUNTER)
        COUNTER.installed = True
    return True


def const_bytes(closed_jaxpr) -> int:
    """Bytes of the array constants a traced program captured (the
    ``consts`` of its `ClosedJaxpr`, e.g. ``jax.jit(f).trace(...).jaxpr``):
    data the compiled program carries in its own text instead of taking
    as an argument."""
    return int(sum(getattr(c, "nbytes", 0) for c in closed_jaxpr.consts))


def counts() -> Dict[str, float]:
    """The process's totals since `install()`: ``lowerings``, backend
    ``compiles`` and their seconds ``compile_s``."""
    install()
    return COUNTER.counts()
