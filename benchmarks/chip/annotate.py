"""The benchmark's own instrumentation around the program's layers.

* :class:`ProfiledTracer`: a ``repro.obs.trace.Tracer`` whose every span
  also enters ``jax.profiler.TraceAnnotation``, so the program's own
  spans land in the profiler's trace on the device's clock.
* :class:`CompileMeter`: backend compiles and persistent-cache loads,
  from JAX's monitoring events.
* :class:`KernelCounter`: elements and blocks handed to each codec and
  fold entry point of ``repro.kernels.ops``, counted by wrapping those
  module attributes while the traced window runs.
"""
from __future__ import annotations

import math
from typing import Any

import jax

from repro.obs.trace import Tracer


class _AnnotatedSpan:
    __slots__ = ("_inner", "_ann")

    def __init__(self, inner: Any, name: str) -> None:
        self._inner = inner
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> Any:
        self._ann.__enter__()
        return self._inner.__enter__()

    def __exit__(self, *exc: Any) -> None:
        self._inner.__exit__(*exc)
        self._ann.__exit__(*exc)


class ProfiledTracer(Tracer):
    def span(self, name: str, cat: str = "", **args: Any) -> Any:
        return _AnnotatedSpan(super().span(name, cat, **args), name)


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is recorded as the load)."""

    def __init__(self) -> None:
        self.compiles = 0
        self.hits = 0

    def install(self) -> None:
        def on_duration(event: str, _secs: float, **_kw: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event: str, **_kw: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[int, int]:
        return self.compiles, self.hits


def _elems_q(x: Any, *_a: Any, **_k: Any) -> int:
    return int(x.size)


def _elems_d8(_q: Any, _am: Any, shape: Any, *_a: Any, **_k: Any) -> int:
    return int(math.prod(shape))


def _elems_d4(_p: Any, _am: Any, _fmt: str, shape: Any, *_a: Any, **_k: Any) -> int:
    return int(math.prod(shape))


def _elems_fold(_acc: Any, q: Any, *_a: Any, **_k: Any) -> int:
    return int(q.shape[0] * q.shape[1])


#: ops entry point -> (kind, elements one call moves, from its arguments)
_COUNTED = {
    "quantize_blockwise8": ("q8", _elems_q),
    "dequantize_blockwise8": ("d8", _elems_d8),
    "quantize_4bit": ("q4", _elems_q),
    "dequantize_4bit": ("d4", _elems_d4),
    "dequant_accumulate8_into": ("fold8", _elems_fold),
}


class KernelCounter:
    """Per kind: elements handed to the ops entry points."""

    def __init__(self) -> None:
        self.elems: dict[str, int] = {}
        self._saved: dict[str, Any] = {}

    def install(self) -> None:
        from repro.kernels import ops

        for attr, (kind, count) in _COUNTED.items():
            fn = getattr(ops, attr, None)
            if fn is None:
                continue
            self._saved[attr] = fn
            setattr(ops, attr, self._wrap(fn, kind, count))

    def _wrap(self, fn: Any, kind: str, count: Any) -> Any:
        def counted(*args: Any, **kwargs: Any) -> Any:
            self.elems[kind] = self.elems.get(kind, 0) + count(*args, **kwargs)
            return fn(*args, **kwargs)

        return counted

    def uninstall(self) -> None:
        from repro.kernels import ops

        for attr, fn in self._saved.items():
            setattr(ops, attr, fn)
        self._saved = {}
