"""Readers for a model of KDA linear-attention and latent-attention layers
over a share of its routed experts: the scopes ``kda_conv``,
``kda_prefill`` and ``kda_decode`` that ``distkeras_tpu.models.hybrid_moe``
makes its operations under, the ``moe_*`` scopes of its expert layers,
and the args ``live`` and ``experts_touched`` that the engine puts on
``dkt:decode_step`` (``experts_touched`` counts the experts held here that
got a row).

As everywhere: a program without the scopes or the args gives a reader
nothing to read, and it returns ``None``.  What is general about a
serving cell is read by ``layers_serve`` and ``layers_moe`` under this
cell's names.
"""

from perfbench import layers_moe, layers_serve


def _share(L, scopes):
    busy = L.busy["busy_s"]
    seconds = layers_moe.scope_seconds(L, scopes)
    return 100.0 * seconds / busy if seconds and busy else None


def kda_decode_device_share(L):
    return _share(L, ("kda_decode",))


def kda_prefill_device_share(L):
    return _share(L, ("kda_prefill",))


def _live_rows(L):
    """Live rows of each of the window's decode steps, from the spans'
    ``live`` (the engine's count when it dispatched the step)."""
    return [s["stats"]["live"] for s in layers_moe._spans(L, "decode_step")
            if "live" in s["stats"]]


def kda_decode_roofline(L):
    """The recurrence of the decode steps (scope ``kda_decode``): each
    live row's state and convolution tail read and written once in every
    KDA layer, a sub-step each, over the scope's device time."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = layers_moe.scope_seconds(L, ("kda_decode",))
    live = _live_rows(L)
    if not seconds or not live:
        return None
    steps = L.ctx.traffic["engine"]["steps_per_sync"]
    nbytes = counts.kda_step_bytes_per_row(cfg) * steps * sum(live)
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds


def decode_roofline(L):
    """The least time of the window's decode steps over the device time
    of the step programs: a step reads the weights outside the routed
    experts, the held experts its rows touched, each live row's recurrent
    state (read and written) and the live requests' latent cache, or
    does its operations at the peak, whichever takes longer."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds, runs = L.trace.program_seconds(L.lines,
                                            layers_serve.STEP_PROGRAM)
    work, touched = layers_moe._decode_work(L)
    if not runs or not seconds or not work:
        return None
    per_context = counts.decode_flops(cfg, 1) - counts.decode_flops(cfg, 0)
    least = 0.0
    for w, n in zip(work, touched):
        nbytes = counts.non_expert_weight_bytes(cfg) \
            + counts.expert_bytes(cfg) * n \
            + counts.kda_step_bytes_per_row(cfg) * w["decode_tokens"] \
            + counts.kv_bytes_per_token(cfg) * w["context_tokens"]
        flops = counts.decode_flops(cfg, 0) * w["decode_tokens"] \
            + per_context * w["context_tokens"]
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds


def moe_experts_roofline(L):
    """As ``layers_moe.moe_experts_roofline``, for a chip that holds a
    share of the experts: the touched held experts' weights read once a
    program, and each token through those of its chosen experts that are
    held here (``counts.held_picks``, in the mean)."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = layers_moe.scope_seconds(L, ("moe_experts",),
                                       layers_moe.GROUPED_PRODUCT)
    layers = counts.expert_layers(cfg)
    calls = [(s, L.slots) for s in layers_moe._spans(L, "decode_step")] \
        + [(s, s["stats"].get("prompt_tokens", 0))
           for s in layers_moe._spans(L, "prefill")]
    if not seconds or not calls:
        return None
    least = 0.0
    for s, tokens in calls:
        nbytes = counts.expert_bytes(cfg) * s["stats"]["experts_touched"]
        flops = counts.expert_flops_per_assignment(cfg) * tokens \
            * counts.held_picks(cfg) * layers
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds


def expert_load_max_over_mean(L):
    """The busiest held expert's rows in a decode step over the mean
    expert's (rows computed x experts a token / all the layer's experts,
    held here or not): 1 is a flat load."""
    steps = layers_moe._spans(L, "decode_step")
    cfg = L.ctx.config
    mean = L.slots * cfg["num_experts_per_tok"] / cfg["num_experts"]
    if not steps or not mean:
        return None
    return sum(s["stats"]["expert_tokens_max"] for s in steps) \
        / len(steps) / mean


def mla_decode_roofline(L):
    """As ``layers_moe.mla_decode_roofline``, over the latent layers
    alone: the live contexts' latent cache read once, the absorbed
    products, over the device time of scope ``mla_decode``."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = layers_moe.scope_seconds(L, ("mla_decode",))
    work = [w for w in layers_serve._steps_in_window(L)
            if w["decode_tokens"]]
    if not seconds or not work:
        return None
    per_token = counts.latent_layers(cfg) \
        * counts.absorbed_flops_per_context_token_layer(cfg)
    least = 0.0
    for w in work:
        nbytes = counts.kv_bytes_per_token(cfg) * w["context_tokens"]
        flops = per_token * float(w["context_tokens"])
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds
