"""Profiling / benchmarking utilities (SURVEY.md §5 "honest
observability": the reference records only wall-clock ``training_time``;
the rebuild ships peak-FLOPs and peak-bandwidth tables, MFU accounting,
safe device-sync timing, and a ``jax.profiler`` trace hook that anchors
the device timeline to the host span clock).

Shared by ``bench.py``, ``distkeras_tpu.attrib`` and the
``scripts/perf_*.py`` experiments so the constants and the timing
workaround live in exactly one place.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator

import jax
import jax.numpy as jnp

#: bf16 peak FLOP/s per chip by device kind (public spec sheets).  The
#: ``"cpu"`` row is a NOMINAL placeholder for CI runs off-TPU — it is
#: deliberately reported as ``known=False`` by :func:`peak_flops` so an
#: MFU computed against it carries an explicit ``peak_known: false``
#: flag instead of looking authoritative.
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "cpu": 1e12,  # nominal, for CI runs off-TPU (known=False)
}

#: HBM bandwidth, bytes/s per chip (public spec sheets) — the
#: denominator of the roofline's communication term.  On the CPU
#: backend collectives are memcpys through host memory; the nominal row
#: keeps the roofline computable there (flagged ``known=False``).
PEAK_BYTES_PER_SEC = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 820e9,
    "TPU v5e": 820e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
    "cpu": 50e9,  # nominal host-memory figure (known=False)
}

#: Analytic forward FLOPs (2 x MACs) per image for ResNet-50 @ 224px
#: (torchvision: 4.09 GMACs).  Training step ~= 3x forward.  See PERF.md
#: §1 for why MFU uses this rather than XLA's executed-FLOPs counter.
RESNET50_FWD_GFLOPS_224 = 8.18

#: Device kinds whose table rows are nominal placeholders, not spec
#: sheets.  A lookup that lands here still returns the value (so CI
#: rooflines stay computable) but with ``known=False`` — callers must
#: surface that flag (``peak_known`` in bench records) rather than let
#: a guessed CPU peak masquerade as measured hardware.
_NOMINAL_KINDS = frozenset({"cpu"})


def _peak_lookup(table: dict, device) -> tuple[float, bool]:
    """Exact match on ``device_kind``: a prefix match would hand an
    unlisted ``"TPU v5x"`` the v5p row and every MFU after it would be
    wrong by the ratio of the two peaks."""
    kind = device.device_kind
    if kind in table:
        return table[kind], kind not in _NOMINAL_KINDS
    return float("nan"), False


def peak_flops(device) -> tuple[float, bool]:
    """(bf16 peak FLOP/s, known?) for ``device``.

    Spec-sheet kinds return ``known=True``.  The CPU backend returns
    its NOMINAL table value with ``known=False`` — usable for relative
    CI gating, but callers must record the flag (``peak_known``)
    instead of presenting the MFU as authoritative.  Unknown kinds
    return ``(nan, False)``; callers must omit or null their MFU
    figures rather than fabricate a peak (ADVICE.md r1).
    """
    return _peak_lookup(PEAK_FLOPS, device)


def peak_bandwidth(device) -> tuple[float, bool]:
    """(peak bytes/s, known?) for ``device`` — same semantics as
    :func:`peak_flops` (nominal CPU row, ``known=False``)."""
    return _peak_lookup(PEAK_BYTES_PER_SEC, device)


def resnet50_model_flops(batch: int, image: int = 224,
                         train: bool = True) -> float:
    """Analytic model FLOPs for one ResNet-50 step."""
    scale = (image / 224) ** 2
    return (RESNET50_FWD_GFLOPS_224 * 1e9 * scale * batch
            * (3 if train else 1))


def require_tpu():
    """The measurement entry points' device gate (``bench.py``,
    ``chip_smoke.py``): ``jax.devices()[0]`` when it is a TPU whose
    ``device_kind`` has a spec-sheet row in the peak tables, else
    ``SystemExit`` before anything is compiled or timed.  A number
    from a CPU run must never appear under a device metric's name, and
    an unlisted kind has no peak to divide by."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0] is {device.platform!r} "
            f"({device.device_kind!r}); this entry point measures the "
            "chip and refuses to run on anything else")
    if not (peak_flops(device)[1] and peak_bandwidth(device)[1]):
        raise SystemExit(
            f"device_kind {device.device_kind!r} has no row in "
            "profiling.PEAK_FLOPS / PEAK_BYTES_PER_SEC; add its "
            "spec-sheet peaks before measuring on it")
    return device


def device_record() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports them — every
    measurement line names the device it ran on."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set this does nothing — JAX
    reads the variable itself and no code sets another directory.
    Otherwise the cache goes to ``<repo>/.jax_cache``, a FIXED path
    computed from this file: the directory is part of the cache key, so
    one derived from a temporary name, a pid or the time never hits.
    Call it first in every entry point that measures on the chip.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def bench_device_config() -> dict:
    """One place for ``bench.py``'s device/shape assumptions: ResNet-50
    at the published shape, on a TPU or not at all (:func:`require_tpu`).
    ``n_devices`` is what ``--mode auto`` keys off.
    """
    device = require_tpu()
    return {
        "device": device,
        "n_devices": len(jax.devices()),
        "batch": 256,
        "image": 224,
        "num_classes": 1000,
    }


def train_mfu(images_per_sec: float, image: int, device,
              n_chips: int = 1) -> float | None:
    """Analytic-model-FLOPs MFU, honest across chip counts: total
    images/sec x FLOPs per training image, over ``n_chips`` x peak.
    Returns ``None`` when the device kind has no peak AT ALL (not even
    a nominal row); a nominal-peak figure is returned but callers must
    pair it with the ``known`` flag from :func:`peak_flops`
    (``peak_known`` in bench records) so it cannot masquerade as a
    measured-hardware number.  Both ``bench.py`` arms and the flagship
    script use THIS accounting, so a mesh number and a single-chip
    number are directly comparable.
    """
    peak, _known = peak_flops(device)
    if peak != peak:  # NaN: no table row, nothing honest to divide by
        return None
    return (resnet50_model_flops(1, image) * images_per_sec
            / (peak * n_chips))


def host_sync(out) -> float:
    """Force full device execution by fetching one scalar to the host
    (a host transfer depends on the whole computation chain).  Returns
    the fetched scalar — the timing loops use it as a finiteness check
    as well as the sync point.
    """
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.real(leaf.reshape(-1)[0]).astype(jnp.float32))


def time_step_chain(step_fn, state, batch, n: int = 20,
                    warmup: int = 2) -> tuple[float, float]:
    """Time ``step_fn(state, batch) -> (state, metrics)`` over a chain.

    Threads the (possibly donated) state through the chain and syncs on
    the final metrics, so it is safe for ``jax.jit(..., donate_argnums=0)``
    functions.  Returns ``(seconds_per_call, synced_metric_scalar)`` —
    the scalar is the first metrics leaf, useful as a finite-ness health
    check.  Divide seconds by the window length yourself when timing
    scanned windows.
    """
    for _ in range(max(warmup, 1)):
        state, metrics = step_fn(state, batch)
    host_sync(metrics)
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = step_fn(state, batch)
    value = host_sync(metrics)
    return (time.perf_counter() - t0) / n, value


def telemetry_overhead(n: int = 200_000) -> dict:
    """Measured per-call cost (ns) of the telemetry hot-path
    primitives, disabled vs enabled — the number PERF.md section 6 quotes
    and ``scripts/obs_report.py`` re-measures.  Restores the global
    telemetry state it found.

    The disabled arm is what every instrumented call site pays when
    telemetry is off and no profiler session runs (the tier-1 /
    perf-row fast path): a registry lookup returning the shared no-op
    metric, and a span that is one inert ``dkt:`` profiler annotation.
    The enabled arm adds the real lock + dict work.
    """
    from distkeras_tpu import telemetry

    def per_call_ns(fn) -> float:
        fn()  # warm any lazy allocation out of the timed loop
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e9

    def inc_op():
        telemetry.metrics().counter("overhead_probe").inc()

    def span_op():
        with telemetry.span("overhead_probe"):
            pass

    prior = telemetry.get() if telemetry.enabled() else None
    out = {}
    try:
        telemetry.disable()
        out["disabled_counter_inc_ns"] = round(per_call_ns(inc_op), 1)
        out["disabled_span_ns"] = round(per_call_ns(span_op), 1)
        telemetry.enable()
        out["enabled_counter_inc_ns"] = round(per_call_ns(inc_op), 1)
        out["enabled_span_ns"] = round(per_call_ns(span_op), 1)
    finally:
        if prior is not None:
            telemetry.enable(telemetry=prior)
        else:
            telemetry.disable()
    return out


@contextlib.contextmanager
def profiler_trace(log_dir: str | None) -> Iterator[None]:
    """``jax.profiler`` trace hook: no-op when ``log_dir`` is None, so
    trainers can accept an optional ``profile_dir`` flag without
    branching at every call site.

    While it is active every ``telemetry.span`` lands in the capture
    as ``dkt:<name>`` on ``/host:CPU``, on the same clock as the
    device's ``XLA Ops``.
    """
    if log_dir is None:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
