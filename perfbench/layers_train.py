"""What the training cells' per-layer readers share; see layers_serve."""


def host_ms_per_step(L):
    """Time a step leaves the device waiting for the host: the traced
    window less the device's busy time, per step."""
    b = L.busy
    return 1e3 * (b["window_s"] - b["busy_s"]) / L.steps if L.steps else None


def step_device_mfu(L):
    """The whole step's operations over the device's busy time and the
    chips' peak."""
    b = L.busy
    if not b["busy_s"] or not L.steps:
        return None
    return 100.0 * L.steps * L.flops_step / b["busy_s"] / (
        L.ctx.chips * L.ctx.peaks["flops_bf16"])


def flash_attn_roofline(L):
    """Forward, dQ and dK/dV kernels: the least time their operations and
    bytes allow over the device time of the Mosaic calls."""
    _, _, counts, _ = L.ctx.arch
    seconds, calls = L.trace.kernel_seconds(L.lines, L.trace.is_mosaic_call)
    if not calls or not seconds:
        return None
    need = counts.flash_attention_train(L.ctx.config, L.batch, L.seq_len)
    least = max(need["flops"] / L.ctx.peaks["flops_bf16"],
                need["bytes"] / L.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * L.steps * least / seconds


def device_idle_share(L):
    b = L.busy
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"]) if b["busy_s"] \
        else None


def peak_hbm_gb(L):
    return L.peak_bytes / 1e9 if L.peak_bytes else None
