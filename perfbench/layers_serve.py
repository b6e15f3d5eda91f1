"""What the serving cells' per-layer readers share.  Each reader in
``perfbench/metrics/`` is one of these under the metric's own name; one
that finds nothing to read returns ``None`` and the metric is left out."""

STEP_SPAN = "bench:engine.step"
STEP_PROGRAM = "jit_step_impl"        # the engine's decode program
PREFILL_PROGRAM = "jit_prefill_impl"  # the engine's prefill program


def _steps_in_window(L):
    return [w for w, e in zip(L.work, L.served.end)
            if L.t_open < e <= L.t_close]


def generator_lateness_p95_ms(L):
    v = L.numbers["lateness_p95_ms"]
    return v if v == v else None


def tpot_p95_ms(L):
    v = L.numbers["tpot_p95_ms"]
    return v if v == v else None


def slot_occupancy_mean(L):
    return L.numbers["occupancy_mean"] or None


def engine_step_host_ms(L):
    """Host time of ``engine.step()`` that the device does not cover: the
    device-idle seconds inside the benchmark's spans around the call, per
    step."""
    total, count, idle = L.trace.span_seconds(L.lines, STEP_SPAN)
    return 1e3 * idle / count if count else None


def decode_step_device_ms(L):
    """Device time of the decode programs of one engine step (all pools)."""
    seconds, runs = L.trace.program_seconds(L.lines, STEP_PROGRAM)
    _, count, _ = L.trace.span_seconds(L.lines, STEP_SPAN)
    return 1e3 * seconds / count if runs and count else None


def prefill_device_share(L):
    seconds, runs = L.trace.program_seconds(L.lines, PREFILL_PROGRAM)
    busy = L.busy["busy_s"]
    return 100.0 * seconds / busy if runs and busy else None


def decode_roofline(L):
    """The least time the chip could take for the window's decode steps,
    each reading the weights once and the live requests' keys and values,
    over the device time of the decode programs."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds, runs = L.trace.program_seconds(L.lines, STEP_PROGRAM)
    if not runs or not seconds:
        return None
    least = 0.0
    for w in _steps_in_window(L):
        if not w["decode_tokens"]:
            continue
        nbytes = counts.weight_bytes(cfg) \
            + counts.kv_bytes_per_token(cfg) * w["context_tokens"]
        per_context = counts.decode_flops(cfg, 1) - counts.decode_flops(cfg, 0)
        flops = counts.decode_flops(cfg, 0) * w["decode_tokens"] \
            + per_context * w["context_tokens"]
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds if least else None


def serve_mfu(L):
    """Operations the window's tokens needed (prefill and decode, no
    padding) over the window and the chip's peak."""
    flops = sum(w["flops"] for w in _steps_in_window(L))
    window = L.busy["window_s"]
    return 100.0 * flops / window / L.ctx.peaks["flops_bf16"] \
        if flops else None


def device_idle_share(L):
    b = L.busy
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"]) if b["busy_s"] \
        else None


def peak_hbm_gb(L):
    return L.peak_bytes / 1e9 if L.peak_bytes else None
