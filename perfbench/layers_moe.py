"""Readers for a model with routed experts, latent attention and a
multi-stream residual: the scopes ``moe_*``, ``mla_decode``,
``latent_write`` and ``hc_mix`` that ``distkeras_tpu.models.latent_moe`` makes its operations
under, and the args ``experts_touched`` / ``expert_tokens_max`` that the
engine puts on ``dkt:decode_step`` and ``dkt:prefill`` once it has
fetched the step's tokens (``DecodeEngine._note_expert_load``).

As everywhere: a program without the scopes or the args (any commit
before this one) gives a reader nothing to read, and it returns ``None``.
What is general about a serving cell is read by ``layers_serve`` and
``layers_spans`` under this cell's names.
"""

import re

from perfbench import layers_serve, layers_spans

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")


# The grouped products are the megablox kernel (``%gmm``), which keeps the
# scope ``moe_experts`` it was called under.  Where the program falls
# back to ``jax.lax.ragged_dot`` XLA rewrites it into a Mosaic call of its
# own, ``%ragged-dot-none``, and that rewrite keeps no ``op_name`` (seen
# in the HLO compiled for a v5e and in the first trace of the cell,
# PR 28): such a call is found by its name.
GROUPED_PRODUCT = re.compile(r"^%?ragged-dot")


def scope_seconds(L, scopes, kernel=None):
    """Own device time, in the traced window, of the operations made
    under any of ``scopes`` or named like ``kernel`` (mean over the
    device planes)."""
    op_names = layers_spans.second_pass(L)["op_names"]
    if not op_names:
        return 0.0
    under = re.compile(r"(?:^|[/(])(?:" + "|".join(map(re.escape, scopes))
                       + r")(?:[/):]|$)")
    t0, t1 = L.trace.traced_window(L.lines)
    planes = L.trace.device_planes(L.lines)
    total = 0.0
    for p in planes:
        line = L.lines.get((p, L.trace.OPS_LINE))
        if line is None:
            continue
        for name, s in L.trace.self_seconds(line.clipped(t0, t1)).items():
            if under.search(op_names.get(name, "")) or \
                    (kernel is not None and kernel.match(name)):
                total += s
    return total / len(planes) if planes else 0.0


def _share(L, scopes, kernel=None):
    busy = L.busy["busy_s"]
    seconds = scope_seconds(L, scopes, kernel)
    return 100.0 * seconds / busy if seconds and busy else None


def moe_device_share(L):
    return _share(L, MOE_SCOPES, GROUPED_PRODUCT)


def mla_decode_device_share(L):
    return _share(L, ("mla_decode",))


def hc_mix_device_share(L):
    return _share(L, ("hc_mix",))


def latent_write_device_share(L):
    """The write of a token's latent into the cache, in the decode steps
    and the prefills (as ``*_kv_write_device_share`` reads ``kv_write``)."""
    return _share(L, ("latent_write",))


def _spans(L, name):
    """The window's ``dkt:<name>`` spans that carry the experts' args."""
    t0, t1 = L.trace.traced_window(L.lines)
    return sorted(
        (s for s in layers_spans.second_pass(L)["spans"]
         if s["name"] == layers_spans.PREFIX + name
         and t0 <= s["start"] < t1 and "experts_touched" in s["stats"]),
        key=lambda s: s["start"])


def experts_touched_mean(L):
    """Distinct experts that got a row in a decode step, summed over the
    expert layers."""
    steps = _spans(L, "decode_step")
    if not steps:
        return None
    return sum(s["stats"]["experts_touched"] for s in steps) / len(steps)


def expert_load_max_over_mean(L):
    """The busiest expert's rows in a decode step over the mean expert's
    (rows computed x experts a token / experts): 1 is a flat load."""
    steps = _spans(L, "decode_step")
    cfg = L.ctx.config
    mean = L.slots * cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    if not steps or not mean:
        return None
    return sum(s["stats"]["expert_tokens_max"] for s in steps) \
        / len(steps) / mean


def _decode_work(L):
    """``(work of the window's decode steps, experts touched in each)``:
    the spans' own count where there is one a step, else their mean."""
    work = [w for w in layers_serve._steps_in_window(L)
            if w["decode_tokens"]]
    steps = _spans(L, "decode_step")
    if not work or not steps:
        return [], []
    touched = [s["stats"]["experts_touched"] for s in steps]
    if len(touched) != len(work):
        touched = [sum(touched) / len(touched)] * len(work)
    return work, touched


def decode_roofline(L):
    """As ``layers_serve.decode_roofline``, with the bytes a step needs
    counted as the weights outside the routed experts, the experts its
    rows touched, and the live requests' latent cache."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds, runs = L.trace.program_seconds(L.lines,
                                            layers_serve.STEP_PROGRAM)
    work, touched = _decode_work(L)
    if not runs or not seconds or not work:
        return None
    per_context = counts.decode_flops(cfg, 1) - counts.decode_flops(cfg, 0)
    least = 0.0
    for w, n in zip(work, touched):
        nbytes = counts.non_expert_weight_bytes(cfg) \
            + counts.expert_bytes(cfg) * n \
            + counts.kv_bytes_per_token(cfg) * w["context_tokens"]
        flops = counts.decode_flops(cfg, 0) * w["decode_tokens"] \
            + per_context * w["context_tokens"]
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds


def moe_experts_roofline(L):
    """The grouped products of the routed experts (the ``gmm`` kernel and
    the activation between its two calls, scope ``moe_experts``), in
    the decode steps and the prefills of the window: the touched
    experts' weights read once a program, and a token through each of its
    chosen experts."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = scope_seconds(L, ("moe_experts",), GROUPED_PRODUCT)
    k = cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    calls = [(s, L.slots) for s in _spans(L, "decode_step")] \
        + [(s, s["stats"].get("prompt_tokens", 0))
           for s in _spans(L, "prefill")]
    if not seconds or not calls:
        return None
    least = 0.0
    for s, tokens in calls:
        nbytes = counts.expert_bytes(cfg) * s["stats"]["experts_touched"]
        flops = counts.expert_flops_per_assignment(cfg) * tokens * k * layers
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds


def mla_decode_roofline(L):
    """The absorbed attention of the decode steps (scope ``mla_decode``):
    the live contexts' latent cache read once, the absorbed products."""
    _, _, counts, _ = L.ctx.arch
    cfg, peaks = L.ctx.config, L.ctx.peaks
    seconds = scope_seconds(L, ("mla_decode",))
    work = [w for w in layers_serve._steps_in_window(L)
            if w["decode_tokens"]]
    if not seconds or not work:
        return None
    layers = cfg["num_hidden_layers"]
    least = 0.0
    for w in work:
        nbytes = counts.kv_bytes_per_token(cfg) * w["context_tokens"]
        flops = layers * counts.absorbed_flops_per_context_token_layer(cfg) \
            * float(w["context_tokens"])
        least += max(nbytes / peaks["hbm_bytes_per_s"],
                     flops / peaks["flops_bf16"])
    return 100.0 * least / seconds


# --- the engine step's idle time, under this cell's names -----------------
# The same readings as ``layers_spans.idle_in_*_ms``; the fetch span here
# also carries the experts' load home with the step's tokens.


def idle_in_admit_ms(L):
    return layers_spans.idle_in_admit_ms(L)


def idle_in_prefill_ms(L):
    return layers_spans.idle_in_prefill_ms(L)


def idle_in_dispatch_ms(L):
    return layers_spans.idle_in_dispatch_ms(L)


def idle_in_fetch_ms(L):
    return layers_spans.idle_in_fetch_ms(L)


def idle_in_emit_ms(L):
    return layers_spans.idle_in_emit_ms(L)
