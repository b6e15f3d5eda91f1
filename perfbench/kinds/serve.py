"""Serving cells: ``DecodeEngine.submit()`` + ``step()`` under generated load.

One thread offers the load and steps the engine, as the engine's own
``run()`` does: requests that are due are submitted between two steps, so
how late the generator ran is at most the step in flight, it is reported,
and it is inside every TTFT because each request is timed from when it
was due.

The engine hands tokens back when a request finishes, so the benchmark
stamps each return of ``step()`` with its own clock and places a request's
tokens on those stamps: the first on the step whose span holds the
result's ``t_first`` (the one thing read from the program, and only to
find the step), the last on the step that returned the result, and the
ones between one a step.  Extra tokens (an admission that prefills and
decodes in one step) share the earlier stamp, so their gap is 0.
"""

import bisect
import collections
import contextlib
import gc
import resource

import numpy as np

from perfbench import common, trafficgen
from perfbench.common import now


def warm_keys(requests, buckets, align) -> dict:
    """One request for every (padded prompt, smallest bucket that holds
    prompt + budget): a superset of what any routing that looks at those
    two can tell apart, so every program of the window is warmed."""
    keys = {}
    for r in requests:
        need = trafficgen.pool_class(buckets, len(r.prompt), r.budget)
        padded = -(-len(r.prompt) // align) * align
        keys.setdefault((padded, need), r)
    return keys


def warm_up(engine, requests, buckets, align) -> int:
    """Run each program once.  A warm request carries its real budget, so
    that it is routed as in the window, and a deadline, the engine's own
    way to take a live request out: it leaves after its first steps."""
    keys = warm_keys(requests, buckets, align)
    for (padded, need), r in sorted(keys.items()):
        engine.submit(r.prompt, max_new_tokens=r.budget, deadline=0.05,
                      request_id=f"warm-{padded}-{need}")
        while engine.has_work():
            engine.step()
    return len(keys)


class Served:
    """What one run saw: step stamps, submissions, results."""

    def __init__(self, requests, t_span0):
        self.requests = requests
        self.t_span0 = t_span0
        self.begin, self.end, self.live = [], [], []
        self.submitted = {}     # index -> host time of submit()
        self.results = {}       # index -> (result dict, step index)

    def note_results(self, out, step):
        for res in out:
            rid = res.get("request_id")
            if isinstance(rid, int):
                self.results[rid] = (res, step)

    def deliveries(self, index):
        """Host stamps of the request's tokens, or [] if it got none."""
        if index not in self.results:
            return []
        res, f = self.results[index]
        n = len(res["tokens"])
        if n == 0 or res.get("t_first") is None:
            return []
        s = self.first_step(index)
        rest, steps = n - 1, f - s
        times = [self.end[s]] * (1 + max(0, rest - steps))
        one_each = min(rest, steps)
        times += self.end[f - one_each + 1:f + 1]
        return times

    def first_step(self, index):
        """The step whose span holds the result's ``t_first``."""
        res, f = self.results[index]
        return min(bisect.bisect_left(self.end, res["t_first"]), f)


def drive(engine, requests, preroll_s, seconds, *, wait_first_tokens,
          trace=None):
    """The pre-roll and the window.  Returns ``(served, t_open, t_close)``
    with the window on step boundaries of the host clock."""
    served = Served(requests, now())
    t0 = served.t_span0
    nxt, n = 0, len(requests)
    t_open = t_close = None
    while True:
        t = now()
        while nxt < n and requests[nxt].due <= t - t0:
            r = requests[nxt]
            engine.submit(r.prompt, max_new_tokens=r.budget,
                          request_id=r.index)
            served.submitted[r.index] = now()
            nxt += 1
        if t_open is None and t - t0 >= preroll_s:
            t_open = t
            served.usage = [resource.getrusage(resource.RUSAGE_SELF)]
            if trace is not None:
                trace.start()
        if t_open is not None and t_close is None and \
                t - t_open >= seconds:
            t_close = t
            served.usage.append(resource.getrusage(resource.RUSAGE_SELF))
            if trace is not None:
                trace.stop()
            n = nxt     # what is due after the close is not offered
        if t_close is not None:
            waiting = wait_first_tokens and engine.load()["queued"] > 0
            if not waiting or t - t_close > 60.0:
                break
        if engine.has_work():
            served.begin.append(now())
            with (trace.span("bench:engine.step") if trace is not None
                  else contextlib.nullcontext()):
                out = engine.step()
            served.end.append(now())
            served.live.append(engine.load()["live"])
            served.note_results(out, len(served.end) - 1)
        else:
            pause = requests[nxt].due - (now() - t0) if nxt < n else 0.001
            if pause > 0:
                import time
                time.sleep(min(pause, 0.002))
    return served, t_open, t_close


def window_numbers(served, t_open, t_close) -> dict:
    """The end-to-end numbers of the window, over all of its work."""
    t0 = served.t_span0
    tokens, gaps, ttft, late = 0, [], [], []
    for r in served.requests:
        times = served.deliveries(r.index)
        tokens += sum(t_open < t <= t_close for t in times)
        gaps += [b - a for a, b in zip(times, times[1:])
                 if t_open < b <= t_close]
        if r.phase == "window" and 0 < r.due <= t_close - t0:
            ttft.append(times[0] - (t0 + r.due) if times else float("inf"))
            if r.index in served.submitted:
                late.append(served.submitted[r.index] - (t0 + r.due))
    span = t_close - t_open
    inside = [i for i, e in enumerate(served.end) if t_open < e <= t_close]
    step_ms = [1e3 * (served.end[i] - served.begin[i]) for i in inside]
    between_ms = [1e3 * (served.begin[i] - served.end[i - 1])
                  for i in inside if i > 0]
    return {
        # where a run reads far off, these say whether one stall did it
        "step_max_ms": max(step_ms, default=0.0),
        "step_sum_s": 1e-3 * sum(step_ms),
        "between_steps_max_ms": max(between_ms, default=0.0),
        "host_cpu_s": sum(getattr(served.usage[1], k)
                          - getattr(served.usage[0], k)
                          for k in ("ru_utime", "ru_stime")),
        "involuntary_switches": served.usage[1].ru_nivcsw
        - served.usage[0].ru_nivcsw,
        "serve_tokens_per_s": tokens / span,
        "ttft_p90_ms": 1e3 * common.percentile(ttft, 90),
        "ttft_p50_ms": 1e3 * common.percentile(ttft, 50),
        "tpot_p95_ms": 1e3 * common.percentile(gaps, 95),
        "tpot_p50_ms": 1e3 * common.percentile(gaps, 50),
        "tpot_p90_ms": 1e3 * common.percentile(gaps, 90),
        "tpot_p99_ms": 1e3 * common.percentile(gaps, 99),
        "lateness_p95_ms": 1e3 * common.percentile(late, 95),
        "tokens_in_window": tokens, "gaps": len(gaps),
        "ttft_requests": len(ttft), "window_s": span,
        "steps_in_window": sum(t_open < e <= t_close for e in served.end),
        "occupancy_mean": float(np.mean(
            [l for l, e in zip(served.live, served.end)
             if t_open < e <= t_close] or [0.0])),
    }


def step_work(served, cfg, counts) -> list:
    """For every engine step, what the algorithm needed in it:
    ``{"decode_tokens", "context_tokens", "prefill_tokens", "flops"}``."""
    work = [{"decode_tokens": 0, "context_tokens": 0, "prefill_tokens": 0,
             "flops": 0.0} for _ in served.end]
    for r in served.requests:
        if r.index not in served.results:
            continue
        times = served.deliveries(r.index)
        if not times:
            continue
        s = served.first_step(r.index)
        t_p = len(r.prompt)
        work[s]["prefill_tokens"] += t_p
        work[s]["flops"] += counts.prefill_flops(cfg, t_p)
        context = t_p
        for t in times[1:]:
            k = bisect.bisect_left(served.end, t)
            context += 1
            work[k]["decode_tokens"] += 1
            work[k]["context_tokens"] += context
            work[k]["flops"] += counts.decode_flops(cfg, context)
    return work


def sample_for_check(served, requests, buckets, seed, t_open, t_close,
                     k=5):
    """Finished requests of the window to compare with the reference: the
    longest, one for every bucket class the traffic reached, the rest
    drawn from the seed."""
    done = []
    for r in requests:
        got = served.results.get(r.index)
        if got and "error" not in got[0] and \
                t_open < served.end[got[1]] <= t_close + 60.0:
            done.append(r)
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xC4EC])
    done.sort(key=lambda r: r.index)
    picked = [max(done, key=lambda r: len(r.prompt) + r.budget)]
    for b in buckets:
        cls = [r for r in done if r not in picked and
               trafficgen.pool_class(buckets, len(r.prompt), r.budget) == b]
        if cls:
            picked.append(cls[int(rng.integers(len(cls)))])
    rest = [r for r in done if r not in picked]
    while len(picked) < k and rest:
        picked.append(rest.pop(int(rng.integers(len(rest)))))
    return picked


def backlog_left_share_min(served, requests, buckets, t_close) -> float:
    """How much of the backlog the close of the window found untouched:
    the least, over pool classes, of the share of the class's offered
    budget tokens that belong to requests with no token delivered by
    ``t_close``.  Near 0 the next faster program runs a queue dry and
    idles a slot: ask for more ``requests`` before that."""
    offered, left = collections.Counter(), collections.Counter()
    for r in requests:
        cls = trafficgen.pool_class(buckets, len(r.prompt), r.budget)
        offered[cls] += r.budget
        times = served.deliveries(r.index)      # ascending
        if not times or times[0] > t_close:
            left[cls] += r.budget
    return min(left[cls] / offered[cls] for cls in offered)


def served_gap(reference, cfg, seed, picked, served, precision="f32"):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``picked``.

    With ``precision`` lowered this is the control: at each position the
    token that the lower precision puts first stands in for the served
    one, and its gap is read against the float32 reference."""
    seqs, rows, toks = [], [], []
    for r in picked:
        out = np.asarray(served.results[r.index][0]["tokens"], np.int32)
        seqs.append(np.concatenate([r.prompt, out]))
        rows.append(len(r.prompt) - 1 + np.arange(len(out)))
        toks.append(out)
    dtype = cfg["weights_as_run"]
    ref = reference.served_logits(cfg, seed, dtype, seqs, rows, "f32")
    if precision != "f32":
        low = reference.served_logits(cfg, seed, dtype, seqs, rows, precision)
        toks = [x.argmax(axis=-1) for x in low]
        del low
    worst, not_first = 0.0, 0
    for logits, tok in zip(ref, toks):
        gap = logits.max(axis=-1) - logits[np.arange(len(tok)), tok]
        worst = max(worst, float(gap.max()))
        not_first += int((gap > 0).sum())
    return worst, int(sum(len(t) for t in toks)), not_first


def check_served(checks, reference, cfg, seed, picked, served,
                 precision="f32") -> None:
    """The served tokens of ``picked`` (or, with ``precision`` lowered,
    the control's) held to the limit of ``served_logit_worst_gap``."""
    if picked:
        gap, n_tok, not_first = served_gap(reference, cfg, seed, picked,
                                           served, precision)
    else:
        gap, n_tok, not_first = float("nan"), 0, 0
    checks.at_most("served_logit_worst_gap", gap, tokens=n_tok,
                   requests=len(picked), not_first=not_first)


def run(ctx) -> dict:
    import jax
    from distkeras_tpu.serving import DecodeEngine

    cfg, traffic = ctx.config, ctx.traffic
    adapter, reference, counts, weights = ctx.arch
    eng = traffic["engine"]
    buckets = {int(k): int(v) for k, v in eng["buckets"].items()}
    align = eng["prefill_align"]
    meter = common.CompileMeter().install()
    stages = {}

    t = now()
    w = weights.make(cfg, ctx.seed, cfg["weights_as_run"])
    variables = adapter.program_variables(w)
    del w
    jax.block_until_ready(variables)
    stages["weights_s"] = now() - t

    t = now()
    model = adapter.program_model(cfg, cfg["n_positions"],
                                  **traffic.get("model_overrides", {}))
    engine = DecodeEngine(model, variables, buckets=buckets,
                          prefill_align=align,
                          steps_per_sync=eng["steps_per_sync"])
    requests = trafficgen.serving_requests(traffic, ctx.seed, ctx.seconds,
                                           cfg["vocab_size"])
    stages["engine_s"] = now() - t

    t = now()
    stages["programs_warmed"] = warm_up(engine, requests, sorted(buckets),
                                        align)
    stages["warm_up_s"] = now() - t
    stages["compiles_in_setup"] = meter.compiles
    stages["cache_hits_in_setup"] = meter.cache_hits

    tracer = common.Tracer(ctx.trace_dir) if ctx.trace else None
    gc.collect()
    gc.freeze()
    compiles0 = meter.compiles
    seconds = min(ctx.seconds, traffic["trace_s"]) if ctx.trace \
        else ctx.seconds
    served, t_open, t_close = drive(
        engine, requests, traffic["preroll_s"], seconds,
        wait_first_tokens=traffic["arrival"] != "backlog", trace=tracer)
    compiles_in_window = meter.compiles - compiles0
    stages["preroll_s"] = t_open - served.t_span0
    setup_s = t_open - ctx.t_process_start

    served.note_results(engine.close(), len(served.end) - 1)
    numbers = window_numbers(served, t_open, t_close)
    peak = common.memory_peak_bytes(ctx.chips)
    slots = sum(buckets.values())
    in_window = [l for l, e in zip(served.live, served.end)
                 if t_open < e <= t_close]

    window_reqs = [r for r in requests if r.phase == "window"]
    if traffic["arrival"] == "backlog":
        numbers["backlog_left_share_min"] = backlog_left_share_min(
            served, requests, sorted(buckets), t_close)
        attempted = [r for r in window_reqs
                     if any(t_open < x <= t_close
                            for x in served.deliveries(r.index))]
    else:
        attempted = [r for r in window_reqs
                     if served.t_span0 + r.due <= t_close]
    failed = sum(
        1 for r in attempted
        if r.index not in served.results
        or served.results[r.index][0].get("error",
                                          "engine_closed") != "engine_closed"
        or not served.deliveries(r.index))

    picked = sample_for_check(served, requests, sorted(buckets), ctx.seed,
                              t_open, t_close,
                              k=traffic.get("check_requests", 5))
    work = step_work(served, cfg, counts)
    del engine, variables
    gc.collect()

    checks = common.Checks(ctx.limits)
    t = now()
    check_served(checks, reference, cfg, ctx.seed, picked, served)
    stages["reference_s"] = now() - t
    checks.at_most("requests_failed", failed)
    checks.at_most("compiles_in_window", compiles_in_window)
    if traffic["arrival"] == "backlog":
        checks.at_most("slots_idle_in_window",
                       slots - min(in_window or [0]))

    return {
        "checks": checks, "attempted": len(attempted), "failed": failed,
        "end_to_end": {**numbers, "setup_s": setup_s},
        "memory_peak_bytes": peak, "stages": stages,
        "layers": {"served": served, "work": work, "t_open": t_open,
                   "t_close": t_close, "tracer": tracer, "numbers": numbers,
                   "slots": slots, "peak_bytes": peak, "picked": picked},
    }
