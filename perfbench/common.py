"""Small shared pieces: the clock, percentiles, the device record, the
compile counter and the list of numbers compared for ``correct``."""

import math
import os
import time

now = time.perf_counter
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def percentile(values, p: float) -> float:
    """Nearest-rank percentile over ALL values; a missing answer is
    ``inf`` and stays in the tail."""
    if not values:
        return math.nan
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


class CompileMeter:
    """Counts backend compilations (a persistent-cache hit passes through
    the same event), so that a run can show none fell in its window."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> "CompileMeter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Checks:
    """The numbers compared for ``correct``, each beside its limit."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows = []

    def at_most(self, name: str, value, **extra) -> None:
        """``value <= limits[name]``; a name without a limit is refused."""
        limit = self.limits[name]
        ok = bool(value == value and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok, **extra})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def lines(self) -> list:
        return [f"check {r['name']}: {r['value']} limit {r['limit']} "
                f"({'ok' if r['ok'] else 'NOT OK'})" for r in self.rows]


class Tracer:
    """``jax.profiler`` around a traced window, with the benchmark's own
    spans; python-level tracing off, so that the host loop is slowed as
    little as a trace allows."""

    def __init__(self, log_dir):
        self.log_dir = log_dir
        self.running = False
        self.t_start = self.t_stop = None
        self._window = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()
        self.t_start = now()
        self.running = True

    def span(self, name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self):
        import jax

        self.t_stop = now()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False
