"""Helpers for code that runs in the chip-owning child (the train worker,
the serving replica, the reference process).  jax is imported inside the
functions: importing this module touches nothing."""

from __future__ import annotations

import contextlib
import logging
import time


class CompileLog:
    """Every backend compile of this process, with its end time and
    seconds, and the persistent cache's hits and misses.  A compile whose
    end falls inside the measured window is a fault of the warm-up."""

    def __init__(self):
        import jax.monitoring

        self.compiles: list[tuple[float, float]] = []   # (end time, seconds)
        self.hits = 0
        self.misses = 0
        self.missed: list[str] = []     # what the persistent cache lacked
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        # jax names a missed program only in a DEBUG record: read those
        # records here and let none of them through to the handlers
        compiler_log = logging.getLogger("jax._src.compiler")
        compiler_log.setLevel(logging.DEBUG)
        compiler_log.addFilter(self._record)

    def _record(self, record: logging.LogRecord) -> bool:
        if record.levelno > logging.DEBUG:
            return True
        text = record.getMessage()
        if "CACHE MISS" in text and len(self.missed) < 64:
            self.missed.append(text.split("'")[1] if "'" in text else text)
        return False

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic(), float(seconds)))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for end, _ in self.compiles if t0 <= end <= t1)

    def snapshot(self) -> dict:
        return {"compiles": list(self.compiles), "hits": self.hits,
                "misses": self.misses, "missed": list(self.missed)}


class Spans:
    """Host spans of the benchmark's own, around its calls into a layer:
    ``with spans("feed_wait"): ...``.  Kept in memory (name -> list of
    seconds); in a traced run each is also a ``TraceAnnotation``
    (``bench/<name>``) so that the device's idle gaps can be laid against
    what the host was doing on the profiler's own clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.seconds: dict[str, list[float]] = {}
        self.recording = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.recording:
            yield
            return
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax.profiler

            ann = jax.profiler.TraceAnnotation(f"bench/{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)

    def summary(self) -> dict:
        return {name: {"n": len(v), "sum_s": sum(v),
                       "mean_ms": 1e3 * sum(v) / len(v)}
                for name, v in self.seconds.items() if v}


def seed_key(seed: int):
    """A PRNG key from any whole number: ``jax.random.key`` alone wraps a
    seed at 2**32, and the driver's seeds are that large.  Weight makers
    take the key as an ARGUMENT of their jitted call, so that one compiled
    program serves every seed."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))


def device_report(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_stats(devices) -> list[dict]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({k: int(v) for k, v in stats.items()
                    if isinstance(v, (int, float))})
    return out


def check_chip(devices, chips: int, require_tpu: bool) -> str | None:
    """Why this machine cannot run the cell, or None.  A measurement path
    that finds no chip fails; it never falls back to the CPU."""
    if require_tpu and devices[0].platform != "tpu":
        return f"no TPU: jax reports {devices[0].platform} devices"
    if len(devices) < chips:
        return f"the cell asks for {chips} chips, jax reports {len(devices)}"
    return None


def start_trace(trace_dir: str) -> None:
    """Start the profiler without its Python tracer: the device planes and
    the ``TraceAnnotation`` spans are what the reduction reads, and a
    Python event per call would slow the host it is measuring."""
    import jax.profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
