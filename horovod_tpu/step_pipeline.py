"""Step-pipeline layer: buffer donation + persistent compilation cache.

Two cheap, always-correct levers:

* **Donation** — a train step is a pipeline ``(params, opt_state) ->
  (params, opt_state)``; without ``donate_argnums`` XLA double-buffers
  every parameter and optimizer-state array (2x the largest HBM
  residents) and inserts defensive copies between steps.
  :func:`donated_step` is ``jax.jit`` with the params/opt-state
  positions donated by default — the call-shape every train step in
  benchmark/ and examples/ uses.

* **Persistent compilation cache** — without one, every invocation
  compiles again a program that hasn't changed (set-up time, ``setup_s``
  of a benchmark cell).  :func:`enable_compilation_cache` points JAX's
  persistent cache at a directory so the second run of the same program
  skips XLA entirely.  ``JAX_COMPILATION_CACHE_DIR`` places it from
  outside and wins; below it the ``HVDT_COMPILATION_CACHE`` knob
  (forwardable by ``hvdtrun --compilation-cache-dir``, engaged for
  workers inside ``hvd.init()``); the root scripts default to
  ``<checkout>/.xla_cache``.

Both are library-level conveniences: hand-rolled ``jax.jit(...,
donate_argnums=...)`` remains first-class everywhere.
"""

from __future__ import annotations

import os
from typing import Optional

from .common import config
from .common.logging_util import get_logger

__all__ = ["enable_compilation_cache", "donated_step", "overlap_step"]

log = get_logger(__name__)

_DISABLED = ("0", "off", "none", "false")
_engaged: Optional[str] = None


def enable_compilation_cache(path: Optional[str] = None, *,
                             default: Optional[str] = None,
                             min_compile_secs: Optional[float] = None
                             ) -> Optional[str]:
    """Engage JAX's persistent XLA compilation cache.

    Where the directory comes from, first match wins:

    1. ``JAX_COMPILATION_CACHE_DIR`` — JAX reads it itself at import;
       this function then sets NO directory in code, so a cache placed
       from outside the program is the one every process uses.
    2. ``path``, else the ``HVDT_COMPILATION_CACHE`` knob (what
       ``hvdtrun --compilation-cache-dir`` forwards).  "off" disables.
    3. ``default`` — the root entry points (chip_smoke.py,
       bench_allreduce.py) pass ``<checkout>/.xla_cache``: a fixed path,
       because the path is part of what a later run must find again.

    With none of the three the call is a no-op returning None.
    ``min_compile_secs`` (default: the
    ``HVDT_COMPILATION_CACHE_MIN_COMPILE_SECS`` knob) filters out
    trivially cheap compilations so the cache holds the train steps, not
    every 10 ms helper jit.  Idempotent; returns the engaged directory.
    An unwritable directory is a warning, not a failed run — callers
    that need the cache (chip_smoke.py) check the returned path.
    """
    global _engaged

    import jax

    from .telemetry import compile_ledger

    # Before anything compiles: whichever of this and hvd.init() runs
    # first puts the process's compile ledger on jax.monitoring.
    compile_ledger.install()
    from_jax_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if from_jax_env:
        path = jax.config.jax_compilation_cache_dir
    else:
        if path is None:
            path = config.get_str("HVDT_COMPILATION_CACHE")
        path = str(path or "").strip() or default
        if path is None or str(path).strip().lower() in _DISABLED:
            return _engaged
        path = os.path.abspath(os.path.expanduser(str(path)))
    if _engaged == path:
        return _engaged
    if min_compile_secs is None:
        min_compile_secs = config.get_float(
            "HVDT_COMPILATION_CACHE_MIN_COMPILE_SECS")
    try:
        os.makedirs(path, exist_ok=True)
        if not from_jax_env:
            jax.config.update("jax_compilation_cache_dir", path)
    except OSError as e:
        log.warning("compilation cache not engaged at %s: %r", path, e)
        return _engaged
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    # Cache small entries too: the knob above is the only filter a
    # user asked for.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _engaged = path
    log.info("persistent compilation cache at %s (min compile %.2fs%s)",
             path, float(min_compile_secs),
             ", from JAX_COMPILATION_CACHE_DIR" if from_jax_env else "")
    return _engaged


def donated_step(fn, *, donate_argnums=(0, 1), compile_cache=None,
                 **jit_kwargs):
    """``jax.jit`` for train steps: donates the carried state buffers
    (``(params, opt_state)`` by default — pass ``donate_argnums`` for
    other call shapes, e.g. ``(0, 1, 2)`` with batch stats) and engages
    the persistent compilation cache (env-transparent: no-op unless the
    knob or ``compile_cache`` names a directory).

    Returns the jitted callable unchanged otherwise — ``.lower()``,
    static args, shard_map bodies all work as with plain ``jax.jit``.
    With telemetry on (``HVDT_TELEMETRY=1``) the callable is wrapped so
    each call's dispatch duration feeds ``hvdt_step_dispatch_seconds``;
    with distributed tracing on (``HVDT_TRACE_DIR``) the same wrapper
    records a ``train.step`` span and advances the deterministic
    per-step trace id (telemetry/trace.py).  Attribute access still
    forwards to the jitted fn; with both off the jitted fn itself is
    returned — zero wrapper objects.
    """
    import jax

    from .telemetry import compile_ledger
    from .telemetry.instrument import wrap_step

    enable_compilation_cache(compile_cache)
    compile_ledger.note_step_program(fn)
    return wrap_step(jax.jit(fn, donate_argnums=donate_argnums,
                             **jit_kwargs))


class _OverlapStep:
    """The :func:`overlap_step` handle: calls forward to the (donated,
    cache-engaged) jitted step; :meth:`run` drives a whole batch stream
    with double-buffered host→device input."""

    __slots__ = ("_fn", "_prefetch", "_sharding", "_put")

    def __init__(self, fn, prefetch: int, sharding, put):
        self._fn = fn
        self._prefetch = prefetch
        self._sharding = sharding
        self._put = put

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def run(self, state, batches):
        """Drive the step over ``batches`` with ``prefetch_size`` device
        batches in flight: batch N+1's h2d transfer (sharding-aware,
        data/loader.prefetch_to_device) rides under step N's compute.

        ``state`` is the tuple of donated leading arguments (e.g.
        ``(params, opt_state)``); each batch is appended as trailing
        argument(s) — a tuple/list batch is splatted.  The step must
        return the next state tuple.  Returns the final state; the
        prefetch generator is closed (queued device buffers dropped)
        even when the loop exits early via an exception.
        """
        from .data.loader import prefetch_to_device

        state = tuple(state)
        it = prefetch_to_device(batches, size=self._prefetch,
                                sharding=self._sharding, put=self._put)
        try:
            for batch in it:
                args = (tuple(batch) if isinstance(batch, (tuple, list))
                        else (batch,))
                out = self._fn(*state, *args)
                state = out if isinstance(out, tuple) else (out,)
        finally:
            it.close()
        return state


def overlap_step(fn, *, donate_argnums=(0, 1), prefetch_size: int = 2,
                 sharding=None, put=None, compile_cache=None,
                 **jit_kwargs) -> _OverlapStep:
    """:func:`donated_step` plus double-buffered host→device input — the
    input half of the overlap scheduling layer (ops/overlap.py is the
    collective half).

    Returns an :class:`_OverlapStep`: call it exactly like the jitted
    step (``.lower()``, attributes, static args all forward), or use
    ``.run(state, batches)`` to drive a whole stream with batch N+1's
    transfer riding under step N.  ``sharding`` may be a single Sharding
    or a pytree of shardings matching each batch (per-leaf placement);
    ``put`` overrides the transfer fn entirely.
    """
    if prefetch_size < 1:
        raise ValueError(
            f"overlap_step needs prefetch_size >= 1 (got {prefetch_size})")
    step = donated_step(fn, donate_argnums=donate_argnums,
                        compile_cache=compile_cache, **jit_kwargs)
    return _OverlapStep(step, prefetch_size, sharding, put)
