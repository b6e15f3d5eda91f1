"""Round attribution: XLA cost extraction + roofline math for the mesh
data plane (ROADMAP item 1 — "where do a round's milliseconds go?").

Three small, dependency-light layers shared by ``MeshDataplane`` (the
cost ledger), ``MeshRoundDriver`` (the sampled step-time decomposition),
and ``bench.py``:

* :func:`extract_cost` — read of ``Compiled.cost_analysis()`` /
  ``memory_analysis()`` for an AOT executable.  ``'flops'`` counts
  PER-DEVICE flops of the SPMD program (verified empirically for the
  shard_map round); absent or malformed analyses degrade to ``None``
  fields, never raise.
* :func:`roofline` — two-term roofline: compute time against a peak
  FLOP/s and communication time against a peak byte/s, classified
  compute- vs comm-bound by arithmetic intensity.  Pure math, unit
  tested against hand-computed numbers.
* :func:`mfu` / :func:`attrib_overhead` — observed-MFU accounting and
  the ``telemetry_overhead``-style microbench bounding the driver's
  disabled-path sampling guard (PERF.md no-op budget).
"""

from __future__ import annotations

import time
from typing import Any

__all__ = [
    "extract_cost",
    "roofline",
    "mfu",
    "attrib_overhead",
]


def extract_cost(compiled: Any) -> dict:
    """Pull {flops, bytes_accessed, peak_temp_bytes, output_bytes,
    argument_bytes, generated_code_bytes} off an AOT ``Compiled``.

    Every field is ``None`` when the backend does not expose it (the
    ledger stays honest instead of guessing); ``flops`` is the
    per-device figure XLA reports for the SPMD partition.
    """
    out: dict[str, Any] = {
        "flops": None,
        "bytes_accessed": None,
        "peak_temp_bytes": None,
        "output_bytes": None,
        "argument_bytes": None,
        "generated_code_bytes": None,
    }
    try:
        cost = compiled.cost_analysis()
    except Exception:
        cost = None
    if cost:
        flops = cost.get("flops")
        if flops is not None and flops >= 0:
            out["flops"] = float(flops)
        nbytes = cost.get("bytes accessed")
        if nbytes is not None and nbytes >= 0:
            out["bytes_accessed"] = float(nbytes)
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for field, attr in (
                ("peak_temp_bytes", "temp_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("argument_bytes", "argument_size_in_bytes"),
                ("generated_code_bytes", "generated_code_size_in_bytes")):
            val = getattr(mem, attr, None)
            if val is not None and val >= 0:
                out[field] = int(val)
    return out


def roofline(flops: float, comm_bytes: float, peak_flops: float,
             peak_bytes_per_sec: float) -> dict:
    """Two-term roofline for one device's share of a round.

    ``t_compute = flops / peak_flops``; ``t_comm = comm_bytes /
    peak_bytes_per_sec``; the predicted round floor is whichever
    dominates, and ``bound`` names it.  ``arithmetic_intensity`` is
    flops per communicated byte — above the machine balance point
    (``peak_flops / peak_bytes_per_sec``) the round is compute-bound.
    Degenerate peaks (zero/NaN) yield a zeroed record rather than a
    division error so unknown devices stay representable.
    """
    flops = max(float(flops or 0.0), 0.0)
    comm_bytes = max(float(comm_bytes or 0.0), 0.0)

    def _finite(x):
        x = float(x or 0.0)
        return x if x > 0.0 and x == x else 0.0

    pf = _finite(peak_flops)
    pb = _finite(peak_bytes_per_sec)
    t_compute = flops / pf if pf else 0.0
    t_comm = comm_bytes / pb if pb else 0.0
    t_roofline = max(t_compute, t_comm)
    intensity = flops / comm_bytes if comm_bytes else float("inf")
    return {
        "t_compute_s": t_compute,
        "t_comm_s": t_comm,
        "t_roofline_s": t_roofline,
        "bound": "compute" if t_compute >= t_comm else "comm",
        "arithmetic_intensity": intensity,
        "machine_balance": (pf / pb) if pb else float("inf"),
    }


def mfu(flops: float, seconds: float, peak_flops: float,
        n_chips: int = 1) -> float | None:
    """Observed model-FLOPs utilization: ``flops`` executed in
    ``seconds`` against ``n_chips x peak_flops``.  ``None`` when any
    term is degenerate (zero time, unknown/NaN peak) — callers must
    null the figure, not fabricate it.
    """
    try:
        flops = float(flops)
        seconds = float(seconds)
        peak_flops = float(peak_flops)
    except (TypeError, ValueError):
        return None
    if (flops <= 0 or seconds <= 0 or n_chips <= 0
            or not peak_flops > 0):  # NaN-safe
        return None
    return flops / seconds / (peak_flops * n_chips)


def attrib_overhead(n: int = 200_000) -> dict:
    """Per-round cost (ns) of the driver's attribution guard when
    sampling is OFF — the exact branch every un-instrumented
    ``MeshRoundDriver.dispatch`` pays (PERF.md no-op budget, measured
    the same way as ``profiling.telemetry_overhead``).

    ``disabled_ns`` is ``attrib_every=0`` (the default: one int test);
    ``armed_unsampled_ns`` is ``attrib_every=N`` on a non-sampled round
    (the guard's modulo plus the end-of-dispatch host-gap clock stamp).
    Both run against the real ``MeshRoundDriver._attrib_tick`` so a
    refactor cannot quietly grow the fast path without this number
    moving.
    """
    from types import SimpleNamespace

    from distkeras_tpu.parallel.ps_dataplane import MeshRoundDriver

    tick = MeshRoundDriver._attrib_tick

    def per_call_ns(fn) -> float:
        fn()
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e9

    off = SimpleNamespace(attrib_every=0, _round_index=0, _last_end=None)
    armed = SimpleNamespace(attrib_every=7, _round_index=1,
                            _last_end=time.perf_counter())

    def off_op():
        off._round_index += 1
        tick(off)

    def armed_op():
        # stay off the sampled residue so only the guard is timed
        armed._round_index += 1
        if armed._round_index % 7 == 0:
            armed._round_index += 1
        tick(armed)
        armed._last_end = time.perf_counter()

    return {
        "disabled_ns": round(per_call_ns(off_op), 1),
        "armed_unsampled_ns": round(per_call_ns(armed_op), 1),
    }
