"""Readers for a model of sliding-window and full grouped-query attention
over a share of its routed experts: the scopes ``swa_decode``,
``gqa_decode``, ``swa_prefill`` and ``gqa_prefill`` that
``distkeras_tpu.models.window_moe`` makes its attention under, the args
``attended_rows`` (the full layers' cache positions a layer read through
the decode kernel) and ``window_rows`` (the ring positions the window
layers read) that the engine puts on ``dkt:decode_step``, and
``band_blocks`` (the key blocks the windowed prefill kernel computed) on
``dkt:prefill``.

As everywhere: a program without the scopes or the args gives a reader
nothing to read, and it returns ``None``.  What is general about a
serving cell is read by ``layers_serve``, ``layers_moe`` and
``layers_kda`` under this cell's names.
"""

import re

from perfbench import layers_moe, layers_serve, layers_spans


def _share(L, scopes):
    busy = L.busy["busy_s"]
    seconds = layers_moe.scope_seconds(L, scopes)
    return 100.0 * seconds / busy if seconds and busy else None


def swa_decode_device_share(L):
    """The window layers' read of their rings in the decode steps."""
    return _share(L, ("swa_decode",))


def gqa_decode_device_share(L):
    """The full layers' read of their whole context in the decode steps."""
    return _share(L, ("gqa_decode",))


def _spans_with(L, name, arg):
    return [s for s in layers_moe._spans(L, name) if arg in s["stats"]]


def attn_decode_roofline(L):
    """Both decode reads (scopes ``swa_decode`` and ``gqa_decode``): the
    positions the kernel read, each of ``KVH`` keys and values
    (``counts.kv_bytes_per_position_layer``), ``attended_rows`` in every
    full layer and ``window_rows`` over all window layers, or their
    products at the peak, over the two scopes' device time."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = layers_moe.scope_seconds(L, ("swa_decode", "gqa_decode"))
    steps = _spans_with(L, "decode_step", "window_rows")
    positions = sum(s["stats"]["attended_rows"] * counts.full_layers(cfg)
                    + s["stats"]["window_rows"] for s in steps)
    if not seconds or not positions:
        return None
    nbytes = positions * counts.kv_bytes_per_position_layer(cfg)
    flops = positions * counts.attention_flops_per_key_layer(cfg)
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["flops_bf16"])
    return 100.0 * least / seconds


def _kernel_seconds_under(L, scope):
    """Own device time, in the traced window, of the Mosaic calls made
    under ``scope`` (mean over the device planes)."""
    op_names = layers_spans.second_pass(L)["op_names"]
    under = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/):]|$)")
    t0, t1 = L.trace.traced_window(L.lines)
    planes = L.trace.device_planes(L.lines)
    total = 0.0
    for p in planes:
        line = L.lines.get((p, L.trace.OPS_LINE))
        if line is None:
            continue
        for name, s in L.trace.self_seconds(line.clipped(t0, t1)).items():
            if L.trace.is_mosaic_call(name) and \
                    under.search(op_names.get(name, "")):
                total += s
    return total / len(planes) if planes else 0.0


def swa_prefill_roofline(L):
    """The windowed flash kernel of the prefills (the Mosaic calls under
    scope ``swa_prefill``): the band's operations that the prompts of the
    window's prefills need (``counts.swa_prefill_flops``, no padding), at
    the peak, over the kernel's device time.  Prefills whose span says
    the kernel computed no block (``band_blocks`` 0: XLA's attention ran)
    are left out."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = _kernel_seconds_under(L, "swa_prefill")
    flops = sum(counts.swa_prefill_flops(cfg, s["stats"]["prompt_tokens"])
                for s in _spans_with(L, "prefill", "band_blocks")
                if s["stats"]["band_blocks"])
    if not seconds or not flops:
        return None
    return 100.0 * flops / peaks["flops_bf16"] / seconds


def decode_roofline(L):
    """The least time of the window's decode steps over the device time of
    the step programs: a step reads the weights outside the routed
    experts, the held experts its rows touched, each live row's rings (a
    whole window of positions: every row of this cell's mix is past its
    first 128 tokens) and the live requests' full-layer cache, or does
    its operations at the peak, whichever takes longer."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds, runs = L.trace.program_seconds(L.lines,
                                            layers_serve.STEP_PROGRAM)
    work, touched = layers_moe._decode_work(L)
    if not runs or not seconds or not work:
        return None
    w_rows = counts.window(cfg)
    per_key = counts.attention_flops_per_key_layer(cfg)
    least = 0.0
    for w, n in zip(work, touched):
        rows = w["decode_tokens"]
        nbytes = counts.non_expert_weight_bytes(cfg) \
            + counts.expert_bytes(cfg) * n \
            + counts.ring_bytes_per_row(cfg, w_rows) * rows \
            + counts.kv_bytes_per_token(cfg) * w["context_tokens"]
        flops = 2.0 * counts.active_params(cfg) * rows + per_key * (
            counts.full_layers(cfg) * w["context_tokens"]
            + counts.window_layers(cfg) * w_rows * rows)
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds
