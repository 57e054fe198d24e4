"""Launcher set-up: meshes, the persistent compile cache, and a clock
for the seconds spent compiling.

``make_production_mesh`` is a FUNCTION (not a module-level constant)
so importing this module never touches jax device state; the dry-run
sets the 512-placeholder-device XLA flag before first jax init.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import time
from typing import Optional

import jax

from repro.models.common import Topology


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_topology(*, multi_pod: bool = False) -> Topology:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    return Topology(mesh=mesh, dp_axes=dp, tp_axis="model")


def make_local_topology(n: Optional[int] = None, tp: int = 1) -> Topology:
    """Mesh over the first ``n`` (default: all) devices this process
    sees — the chips of one host, or virtual CPU devices in tests."""
    n = n or jax.device_count()
    devices = jax.devices()[:n]
    dp = n // tp
    if tp > 1:
        mesh = jax.make_mesh((dp, tp), ("data", "model"), devices=devices)
        return Topology(mesh=mesh, dp_axes=("data",), tp_axis="model")
    mesh = jax.make_mesh((dp,), ("data",), devices=devices)
    return Topology(mesh=mesh, dp_axes=("data",), tp_axis=None)


#: the checkout's own compile-cache directory (``src/repro/launch`` is
#: three levels below the checkout root)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing is set here; otherwise the cache is
    the fixed ``<checkout>/.jax_cache``, so the next process finds what
    this one compiled.  Entry points call it; importing the library
    does not."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


#: jax.monitoring duration events that make up compile time: tracing,
#: lowering, and either compiling or reading the persistent cache
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
))


class CompileClock:
    """Seconds JAX spent compiling while the clock ran (see
    :func:`compile_clock`)."""

    def __init__(self):
        self._spans: list = []

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()  # the event is reported as it ends
            self._spans.append((end - duration, end))

    @property
    def seconds(self) -> float:
        """Length of the union of the compile spans: tracing a jitted
        function traces the jitted functions it calls, and their events
        nest inside its own."""
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


@contextlib.contextmanager
def compile_clock():
    """Count the seconds JAX spends tracing, lowering and compiling in
    this block, so a wall time can be split into compile and run::

        with compile_clock() as cc:
            ...
        run_s = wall_s - cc.seconds
    """
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock._on_event)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(clock._on_event)
