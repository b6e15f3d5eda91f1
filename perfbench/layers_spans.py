"""Readers of what the program records about itself (PR 26): the ``dkt:``
spans that ``distkeras_tpu.telemetry.span`` writes into the profiler's
trace, the times a request carries in its result (``t_admit``,
``t_tokens``), and the ``jax.named_scope`` an operation was made under.

Each reader in ``perfbench/metrics/`` is one of these under the metric's
own name.  A program without the spans, the fields or the scopes (any
commit before PR 26) gives every reader nothing to read: it returns
``None`` and the metric is left out.

Two things ``perfbench/trace.py`` does not keep are read in one second
pass over the ``.xplane.pb``, made once a run and shared (``second_pass``):

* the stats of the ``dkt:`` host events, which are the spans' args;
* the ``op_name`` of each device operation.  It is static, so the
  profiler keeps it on the event's *metadata* (``XEventMetadata.stats``),
  which ``ProfileData`` does not show: ``xplane_metadata`` reads the
  file's ``event_metadata`` and ``stat_metadata`` maps with a protobuf
  wire reader of its own.  On a TPU v5 lite an ``XLA Ops`` event is
  named by its HLO text, and the stat that holds the ``op_name`` is
  ``tf_op``, as ``<op_name>:`` (seen by hand in a trace of
  ``cgpt-serve-steady``, PR 26: 3571 of 11280 operations have it; the
  other string stats are ``hlo_category``, ``shape_with_layout``,
  ``deduplicated_name``, ``source`` and ``source_stack``).  The join is
  on the event's name: two operations of two programs with the same HLO
  text share one entry.
"""

import glob
import os
import re

from perfbench import common

PREFIX = "dkt:"
OP_NAME_STAT = "tf_op"

# --- the protobuf wire format, as far as an XSpace needs it --------------


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed-width field, a ``memoryview`` for a length-delimited
    one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"wire type {wire} is not one an XSpace uses")
        yield number, wire, value


def _map_values(entries):
    """The value messages of a protobuf map field's entries."""
    for entry in entries:
        for number, _, value in fields(entry):
            if number == 2:
                yield value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def xplane_metadata(data) -> dict:
    """``{plane name: {event name: {stat name: text}}}`` of the string
    stats kept on the planes' event metadata (``str_value``, or a
    ``ref_value`` into the plane's stat metadata).  Field numbers, from
    ``xplane.proto``: XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata.id 1, name 2, stats 5;
    XStat.metadata_id 1, str_value 5, ref_value 7; XStatMetadata.id 1,
    name 2."""
    out = {}
    for number, _, plane in fields(data):
        if number != 1:
            continue
        name, events, stats = "", [], []
        for n, _, v in fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                events.append(v)
            elif n == 5:
                stats.append(v)
        stat_names = {}
        for meta in _map_values(stats):
            f = {n: v for n, _, v in fields(meta)}
            stat_names[f.get(1, 0)] = _text(f.get(2, b""))
        by_event = out.setdefault(name, {})
        for meta in _map_values(events):
            ev_name, ev_stats = "", {}
            for n, _, v in fields(meta):
                if n == 2:
                    ev_name = _text(v)
                elif n == 5:
                    f = {k: x for k, _, x in fields(v)}
                    if 5 in f:
                        ev_stats[stat_names.get(f.get(1))] = _text(f[5])
                    elif 7 in f:
                        ev_stats[stat_names.get(f.get(1))] = \
                            stat_names.get(f[7], "")
            if ev_stats:
                by_event[ev_name] = ev_stats
    return out


# --- the second pass ------------------------------------------------------


def read_second_pass(log_dir: str) -> dict:
    """``{"spans": [{"name", "start", "dur", "stats"}], "op_names": {event
    name: op_name}}`` of the newest trace under ``log_dir``: the ``dkt:``
    host events with their stats, and the device operations' ``op_name``."""
    from jax.profiler import ProfileData

    from perfbench import trace

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"spans": [], "op_names": {}}
    with open(paths[-1], "rb") as f:
        raw = f.read()
    spans = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append({"name": ev.name, "start": ev.start_ns,
                                  "dur": ev.duration_ns,
                                  "stats": dict(ev.stats)})
    op_names = {}
    for plane, events in xplane_metadata(raw).items():
        if not plane.startswith(trace.DEVICE_PREFIX):
            continue
        for event, stats in events.items():
            if OP_NAME_STAT in stats:
                op_names[event] = stats[OP_NAME_STAT]
    return {"spans": spans, "op_names": op_names}


def second_pass(L) -> dict:
    """The run's second pass, made when the first reader asks for it."""
    if getattr(L, "spans_pass", None) is None:
        L.spans_pass = read_second_pass(L.ctx.trace_dir)
    return L.spans_pass


# --- device-idle time inside the engine's spans ---------------------------


def _idle_ms_per_step(L, plus=(), minus=()):
    """Device-idle milliseconds inside the spans ``plus`` less those
    inside ``minus`` (their children), per ``dkt:engine_step``."""
    _, steps, _ = L.trace.span_seconds(L.lines, PREFIX + "engine_step")
    if not steps:
        return None
    idle = sum(L.trace.span_seconds(L.lines, PREFIX + n)[2] for n in plus) \
        - sum(L.trace.span_seconds(L.lines, PREFIX + n)[2] for n in minus)
    return 1e3 * idle / steps


def idle_in_admit_ms(L):
    """Both admission passes, less the prefills they hold."""
    return _idle_ms_per_step(L, plus=("admit",), minus=("prefill",))


def idle_in_prefill_ms(L):
    return _idle_ms_per_step(L, plus=("prefill",))


def idle_in_dispatch_ms(L):
    return _idle_ms_per_step(L, plus=("decode_dispatch",))


def idle_in_fetch_ms(L):
    return _idle_ms_per_step(L, plus=("decode_fetch",))


def idle_in_emit_ms(L):
    """The per-slot token loop and the deadline sweep after it."""
    return _idle_ms_per_step(L, plus=("emit", "sweep"))


def idle_in_loss_fetch_ms(L):
    """Training: device-idle time inside the trainer's dispatch of a chunk
    and its fetch of the chunk's losses, per step."""
    spans = [L.trace.span_seconds(L.lines, PREFIX + n)
             for n in ("loss_fetch", "chunk_dispatch")]
    if not L.steps or not any(count for _, count, _ in spans):
        return None
    return 1e3 * sum(idle for _, _, idle in spans) / L.steps


# --- a request's times, read where the program stamps them ----------------


def _window_results(L):
    """``(request, result, step)`` of the window's requests that carry
    the program's own stamps."""
    t0 = L.served.t_span0
    for r in L.served.requests:
        got = L.served.results.get(r.index)
        if got is None or not got[0].get("t_tokens") \
                or got[0].get("t_admit") is None:
            continue
        if r.phase == "window" and 0 < r.due <= L.t_close - t0:
            yield r, got[0], got[1]


def _p_ms(values, p):
    return 1e3 * common.percentile(values, p) if values else None


def queue_wait_p90_ms(L):
    return _p_ms([res["t_admit"] - res["t_submit"]
                  for _, res, _ in _window_results(L)], 90)


def admit_to_first_token_p90_ms(L):
    return _p_ms([res["t_tokens"][0] - res["t_admit"]
                  for _, res, _ in _window_results(L)], 90)


def first_token_handback_p90_ms(L):
    """From the first token on the host to the return of the ``step()``
    that delivered it, which is where the benchmark places it."""
    return _p_ms([L.served.end[L.served.first_step(r.index)]
                  - res["t_tokens"][0]
                  for r, res, _ in _window_results(L)], 90)


def tpot_produced_p95_ms(L):
    """Gaps between consecutive tokens of a request by the program's own
    stamps, those whose later stamp lies in the window."""
    gaps = []
    for res, _ in L.served.results.values():
        times = res.get("t_tokens") or []
        gaps += [b - a for a, b in zip(times, times[1:])
                 if L.t_open < b <= L.t_close]
    return _p_ms(gaps, 95)


# --- span args and scopes, from the second pass ---------------------------


def prefill_useful_token_share(L):
    """Prompt tokens over padded tokens of the window's prefills."""
    t0, t1 = L.trace.traced_window(L.lines)
    prompt = padded = 0
    for s in second_pass(L)["spans"]:
        if s["name"] == PREFIX + "prefill" and t0 <= s["start"] < t1 \
                and "padded" in s["stats"]:
            prompt += s["stats"]["prompt_tokens"]
            padded += s["stats"]["padded"]
    return 100.0 * prompt / padded if padded else None


def _scope_device_share(L, scope):
    """Own time of the operations made under ``scope`` over the device's
    busy time; nothing where no operation of the window is under it."""
    op_names = second_pass(L)["op_names"]
    busy = L.busy["busy_s"]
    if not op_names or not busy:
        return None
    # one component of the op_name's path, bare or inside a transform's
    # brackets: ".../kv_write/scatter:", "transpose(jvp(forward_loss))"
    under = re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/):]|$)")
    t0, t1 = L.trace.traced_window(L.lines)
    planes = L.trace.device_planes(L.lines)
    total = 0.0
    for p in planes:
        line = L.lines.get((p, L.trace.OPS_LINE))
        if line is None:
            continue
        for name, s in L.trace.self_seconds(line.clipped(t0, t1)).items():
            if under.search(op_names.get(name, "")):
                total += s
    return 100.0 * total / len(planes) / busy if total else None


def kv_write_device_share(L):
    return _scope_device_share(L, "kv_write")


def optimizer_device_share(L):
    return _scope_device_share(L, "optimizer_update")
