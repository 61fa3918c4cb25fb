"""JAX-level device telemetry: recompile counters, transfer byte counters,
device sync-wait accounting.

Three legs, all feeding the metrics registry AND the active cycle's
flight record (cook_tpu/utils/flight.py) so a recompile storm or transfer
regression is attributed to the exact cycle whose p99 it blew:

* :func:`instrument_jit` wraps a jitted kernel entry point; each call
  compares the jit cache size before/after, so a tracing/compilation
  (shape change, new static arg) increments
  ``cook_jit_compile_total{kernel=...}``, tags the enclosing tracing span,
  and lands on the owning CycleRecord.  Every kernel in cook_tpu/ops and
  the fused pool-cycle executable are wrapped at definition site.

* :func:`count_transfer` / :func:`sync_wait` are called by the dispatch
  paths (sched/fused.py staging + fetch, sched/matcher.py kernel runs)
  around ``device_put``/``copy_to_host_async``-style boundaries:
  ``cook_device_transfer_bytes_total{direction=h2d|d2h}`` plus
  ``cook_sync_wait_seconds`` for time spent blocked on the device.

* :func:`install_jax_monitoring` (opt-in, COOK_JAX_MONITORING=1 or an
  explicit call) forwards ``jax.monitoring`` events into
  ``cook_jax_event_total{event=...}`` — the firehose view when the
  per-kernel counters aren't enough.
"""

from __future__ import annotations

import functools
import os
import time
from contextlib import contextmanager
from typing import Any, Optional

from ..utils import tracing
from ..utils.flight import recorder
from ..utils.metrics import registry


def _on_compile(kernel: str, n: int) -> None:
    registry.counter_inc("cook_jit_compile", float(n), {"kernel": kernel})
    recorder.note_recompile(kernel, n)
    sp = tracing.tracer.current()
    if sp is not None:
        sp.set_tag("recompiles", int(sp.tags.get("recompiles", 0)) + n)
        sp.set_tag("recompiled_kernel", kernel)


class KernelBuildError(RuntimeError):
    """A kernel entry point raised on an argument signature it has never
    completed with in this process: a trace, lowering or compile error
    (Mosaic refusing a Pallas kernel, an API the installed JAX dropped),
    or a first-run failure that cannot be told from one.  Unlike a
    runtime fault on a known-good executable it repeats on every call,
    so the degrade paths (docs/ROBUSTNESS.md) re-raise it instead of
    counting it on ``cook_kernel_fallback_total`` forever."""

    def __init__(self, kernel: str, cause: BaseException):
        super().__init__(f"kernel {kernel} failed on first use "
                         f"({type(cause).__name__}: {cause})")
        self.kernel = kernel


def _signature(args, kwargs) -> tuple:
    """(shape, dtype) per argument leaf — what selects a jit executable,
    cheap enough for a once-per-cycle dispatch.  Leaves without a shape
    (python scalars, static strings) key on their type only."""
    import jax
    return tuple(
        (getattr(leaf, "shape", None), getattr(leaf, "dtype", type(leaf)))
        for leaf in jax.tree_util.tree_leaves((args, kwargs)))


class InstrumentedJit:
    """Transparent wrapper over a jitted callable that detects cache
    growth (= a fresh trace+compile) per call, and splits failures into
    runtime faults (re-raised as they are; callers may degrade) and
    :class:`KernelBuildError` (first use of an executable; nothing may
    absorb it).  Attribute access (lower, _cache_size, static argname
    plumbing) forwards to the wrapped fn."""

    def __init__(self, kernel: str, fn):
        self._kernel = kernel
        self._fn = fn
        # argument signatures this entry point has completed with at
        # least once: the line between a runtime fault and a build error
        self._proven: set = set()
        try:
            functools.update_wrapper(self, fn, updated=())
        except Exception:  # jit objects without full wrapper attrs
            pass

    def __call__(self, *args, **kwargs):
        fn = self._fn
        sig = _signature(args, kwargs)
        before = fn._cache_size()
        try:
            out = fn(*args, **kwargs)
        except Exception as exc:
            if sig in self._proven:
                raise  # a known-good executable failed: a runtime fault
            raise KernelBuildError(self._kernel, exc) from exc
        self._proven.add(sig)
        # every SUCCESSFUL call through an instrumented entry point is
        # one device kernel dispatch: the per-cycle launch count
        # (ISSUE 14) falls out of the wrapper every kernel already
        # passes through.  Counted after the call — a dispatch that
        # raises (injected fault, device loss) never launched, and
        # charging it would double-count against its fallback
        registry.counter_inc("cook_kernel_launches", 1.0,
                             {"kernel": self._kernel})
        recorder.note_kernel_launch(self._kernel)
        after = fn._cache_size()
        if after > before:
            _on_compile(self._kernel, after - before)
        return out

    def __getattr__(self, name: str) -> Any:
        return getattr(self.__dict__["_fn"], name)


def instrument_jit(kernel: str, fn) -> InstrumentedJit:
    """Wrap a jitted entry point with per-kernel compile counting."""
    return InstrumentedJit(kernel, fn)


def count_transfer(direction: str, nbytes: int) -> None:
    """Record ``nbytes`` crossing the host<->device boundary
    (direction: "h2d" or "d2h")."""
    if nbytes:
        registry.counter_inc("cook_device_transfer_bytes", float(nbytes),
                             {"direction": direction})
        recorder.note_transfer(direction, nbytes)


@contextmanager
def sync_wait(kind: str = "fetch"):
    """Time a block that waits on the device (device_get / block_until_
    ready): observed on ``cook_sync_wait_seconds{kind=}`` and summed into
    the cycle record's sync_wait_ms."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        registry.observe("cook_sync_wait_seconds", dt, {"kind": kind})
        recorder.note_sync_wait(dt)


#: the compile cache of a checkout that configures none (git-ignored).
#: A fixed path, never a temp name: the directory is part of JAX's cache
#: key, so a cache that moves never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache(configured: str = "") -> Optional[str]:
    """The ONE place the persistent compilation cache is placed.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    directory is set in code (whoever runs the process owns the
    placement).  Unset: the configured ``compilation_cache_dir``, else
    :data:`DEFAULT_CACHE_DIR` — the default on a TPU only, because the
    XLA:CPU executables it would cache are pinned to the build machine's
    CPU features and tier-1 must not fill a checkout with them.
    Returns the directory in effect (None = no cache).

    The min-compile-time / min-entry-size floors are dropped either way:
    compile-once-per-fleet beats the write-amplification guard for a
    scheduler whose kernel set is small and stable."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if path is None:
        path = configured or (
            DEFAULT_CACHE_DIR if jax.default_backend() == "tpu" else None)
        if path is None:
            return None
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info() -> dict:
    """Where this process's kernels run, as JAX reports it — the
    ``device`` block of /debug/health and every CycleRecord."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


_monitoring_installed = False


def install_jax_monitoring() -> bool:
    """Forward jax.monitoring events into the metrics registry.  Opt-in
    (global listener, so tests and embedders choose); returns True when
    the listeners are installed."""
    global _monitoring_installed
    if _monitoring_installed:
        return True
    from jax import monitoring
    monitoring.register_event_listener(
        lambda event, **kw: registry.counter_inc(
            "cook_jax_event", 1.0, {"event": event}))
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: registry.observe(
            "cook_jax_event_duration_seconds", duration, {"event": event}))
    _monitoring_installed = True
    return True


if os.environ.get("COOK_JAX_MONITORING"):  # pragma: no cover - env opt-in
    install_jax_monitoring()
