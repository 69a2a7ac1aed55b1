"""Small pieces the harness and the drivers share."""
from __future__ import annotations

import importlib.util
import pathlib
import sys
import time

import jax
import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (its name may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class CompileMeter:
    """Backend compile seconds and persistent-cache hits and misses, read
    from JAX's monitoring events (a cache hit records its load time)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Spans:
    """Host-clock spans around calls into one layer of the program, each
    also written to the profiler's trace as ``bench:<name>`` so that idle
    gaps on the device can be put down to what the host was doing."""

    def __init__(self) -> None:
        self.spans: dict[str, list[tuple[float, float]]] = {}

    def wrap(self, obj, method: str, name: str | None = None, before=None):
        """Replace ``obj.method`` on the instance by a timed call.
        ``before``, when given, is called first with the arguments and its
        result kept beside the span."""
        name = name or method
        fn = getattr(obj, method)
        out = self.spans.setdefault(name, [])
        label = f"bench:{name}"

        def timed(*args, **kwargs):
            extra = before(*args, **kwargs) if before is not None else None
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                result = fn(*args, **kwargs)
            out.append((t0, time.perf_counter(), extra))
            return result

        setattr(obj, method, timed)

    def within(self, name: str, t0: float, t1: float) -> list[tuple]:
        """The spans of ``name`` that started inside ``[t0, t1]``."""
        return [s for s in self.spans.get(name, ()) if t0 <= s[0] <= t1]


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between closest ranks (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
