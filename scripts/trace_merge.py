"""Trace merge — stitch per-process Perfetto/Chrome trace dumps into
one timeline with cross-process flow arrows (ISSUE 6 tentpole 1).

Each process dumps its own ring (``Tracer.write_chrome_trace``): the
PS server process holds the ``ps_rpc`` handler spans, every trainer
process holds its workers' ``ps_client_*`` spans.  The 17-byte wire
trace header (``parallel.transport.trace_header``) links them: the
client stamps its span id on the request and emits a flow-start
("s"), the server handler emits the matching flow-end ("f") — so
after ``telemetry.merge_traces`` aligns the wall clocks, Perfetto
draws an arrow from each surviving commit/pull to the handler that
served it, and a retry storm under ``ChaosTransport`` reads as one
causal chain (shared ``trace_id`` from the ``ps_op`` retry-loop
span).

Two modes:

* ``--out merged.json a.json b.json ...`` — merge trace files an
  earlier multi-process run wrote.
* ``--smoke`` — self-contained two-process proof (the tier-1
  registration): spawns a REAL second Python process hosting a
  ``PSServer``, trains against it over the socket wire with mild
  client-side chaos, dumps one trace per process, merges them, and
  asserts every server-side flow-end pairs with exactly one
  client-side flow-start across the process boundary.

(``--serve`` is the internal child-process mode of the smoke.)
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import pathlib
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

def _mlp_config():
    from distkeras_tpu.models import model_config

    return model_config("mlp", (8,), num_classes=4, hidden=(16,))


def _center():
    """Deterministic center: both processes derive the identical
    template, so the child's server serves the parent's model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.models import ModelSpec

    model = ModelSpec.from_config(_mlp_config()).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.float32))
    return (jax.tree_util.tree_map(np.asarray, variables["params"]),
            variables)


# ---- merge -------------------------------------------------------------

def merge_files(paths: list[str], out: str) -> dict:
    from distkeras_tpu import telemetry

    traces = [json.load(open(p)) for p in paths]
    merged = telemetry.merge_traces(*traces)
    pathlib.Path(out).write_text(json.dumps(merged))
    return merged


def summarize(merged: dict) -> str:
    events = merged["traceEvents"]
    pids = sorted({e["pid"] for e in events if "pid" in e})
    spans = collections.Counter(e["name"] for e in events
                                if e.get("ph") == "X")
    starts = [e for e in events if e.get("ph") == "s"]
    ends = [e for e in events if e.get("ph") == "f"]
    lines = [f"merged {len(events)} events across "
             f"{len(pids)} process tracks {pids}",
             f"flow arrows: {len(starts)} starts, {len(ends)} ends"]
    for name, n in spans.most_common():
        lines.append(f"  span {name:<24} n={n}")
    return "\n".join(lines)


def check_flow_pairing(merged: dict) -> int:
    """Every flow-end must match exactly ONE flow-start by (cat, id);
    orphan starts are legal (a chaos-eaten message has a sender but no
    handler).  Returns the number of paired arrows."""
    events = merged["traceEvents"]
    starts = collections.Counter(
        (e["cat"], e["id"]) for e in events if e.get("ph") == "s")
    ends = [(e["cat"], e["id"]) for e in events if e.get("ph") == "f"]
    for key in ends:
        assert starts.get(key, 0) == 1, (
            f"flow-end {key} has {starts.get(key, 0)} matching "
            f"starts (want exactly 1)")
    return len(ends)


# ---- smoke: the child (PS server) process ------------------------------

def serve(trace_out: str) -> None:
    """Child-process body: host a traced ``PSServer`` until the parent
    closes our stdin, then dump this process's trace and exit."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.parallel.host_ps import (HostParameterServer,
                                                PSServer)
    from distkeras_tpu.parallel.update_rules import DownpourRule

    telemetry.enable()
    center, _ = _center()
    ps = HostParameterServer(DownpourRule(), center)
    srv = PSServer(ps, center).start()
    print(f"PORT {srv.address[1]}", flush=True)
    sys.stdin.readline()  # parent closes stdin / sends a line: done
    srv.stop()
    telemetry.tracer().write_chrome_trace(trace_out)
    print(f"COMMITS {ps.num_commits}", flush=True)


# ---- smoke: device-trace alignment (ISSUE 17) --------------------------

def device_alignment_case(out_dir: str) -> None:
    """Unified host+device timeline: capture a ``jax.profiler`` device
    trace around a host tracer span, load it via
    ``telemetry.load_device_trace`` (wall anchor from
    ``profiling.profiler_trace``), merge with the host dump, and assert
    the device events land inside the host capture span's wall window.
    Skips cleanly when the profiler can't capture on this backend."""
    from distkeras_tpu import profiling, telemetry

    log_dir = pathlib.Path(out_dir) / "device_profile"
    host_path = pathlib.Path(out_dir) / "trace-host.json"
    telemetry.enable()
    try:
        import jax
        import jax.numpy as jnp

        with profiling.profiler_trace(str(log_dir)):
            with telemetry.span("device_capture"):
                f = jax.jit(lambda x: (x @ x.T).sum())
                f(jnp.ones((256, 256), jnp.float32)).block_until_ready()
    except Exception as e:  # profiler backend unavailable here
        telemetry.disable()
        print("device-trace alignment: skipped "
              f"({type(e).__name__}: {e})")
        return
    telemetry.tracer().write_chrome_trace(host_path)
    telemetry.disable()

    device_paths = profiling.find_device_traces(str(log_dir))
    if not device_paths:
        print("device-trace alignment: skipped "
              "(profiler produced no device trace)")
        return
    device = telemetry.load_device_trace(device_paths[0])
    assert "wallAnchor" in device, \
        "profiler_trace wall anchor not found next to the capture"
    # tag device events so they stay identifiable post-merge
    for e in device["traceEvents"]:
        if isinstance(e, dict):
            e["cat"] = "device:" + str(e.get("cat", ""))
    host = json.load(open(host_path))
    merged = telemetry.merge_traces(host, device)  # host anchor = base
    pathlib.Path(out_dir, "merged-device.json").write_text(
        json.dumps(merged))

    events = merged["traceEvents"]
    caps = [e for e in events if e.get("ph") == "X"
            and e["name"] == "device_capture"]
    assert caps, "host capture span missing from merged timeline"
    dev_ts = [e["ts"] for e in events
              if str(e.get("cat", "")).startswith("device:")
              and "ts" in e]
    assert dev_ts, "no device events survived the merge"
    # device events happened INSIDE the host capture span; allow
    # generous slack for profiler start/stop bookkeeping outside it
    lo = caps[0]["ts"] - 5e6
    hi = caps[0]["ts"] + caps[0].get("dur", 0.0) + 5e6
    mid = (min(dev_ts) + max(dev_ts)) / 2.0
    assert lo <= mid <= hi, (
        f"device events not aligned with the host capture window: "
        f"device mid ts {mid} outside [{lo}, {hi}]")
    print(f"device-trace alignment: {len(dev_ts)} device events "
          f"aligned into the host capture window "
          f"({device_paths[0].rsplit('/', 1)[-1]})")


# ---- smoke: the parent (trainer) process -------------------------------

def smoke(out_dir: str) -> None:
    from distkeras_tpu import telemetry
    from distkeras_tpu.data import datasets
    from distkeras_tpu.parallel.faults import ChaosTransport
    from distkeras_tpu.trainers import DOWNPOUR

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    server_trace = out / "trace-server.json"
    client_trace = out / "trace-client.json"

    # The PS child is pinned to the CPU: it only builds a center and
    # serves host numpy, and a chip belongs to one process — the
    # trainer in this parent.
    child = subprocess.Popen(
        [sys.executable, __file__, "--serve", str(server_trace)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=str(REPO), env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        port_line = child.stdout.readline().split()
        assert port_line and port_line[0] == "PORT", port_line
        port = int(port_line[1])

        telemetry.enable()
        _, variables = _center()
        data = datasets.synthetic_classification(512, (8,), 4, seed=0)
        # mild client-side chaos: a couple of scheduled resets force
        # the resilient client's retry path, so the merged trace shows
        # a retry chain under one ps_op trace id
        with ChaosTransport(seed=3, reset_rate=0.08,
                            max_injections=2, skip_ops=6):
            t = DOWNPOUR(_mlp_config(), fidelity="host",
                         transport="socket",
                         ps_address=("127.0.0.1", port),
                         num_workers=2, communication_window=2,
                         batch_size=16, num_epoch=1,
                         learning_rate=0.01,
                         worker_optimizer="adam", worker_retries=8)
            t.train(data, initial_variables=variables)
        telemetry.tracer().write_chrome_trace(client_trace)
        telemetry.disable()
    finally:
        child.stdin.close()
        child.wait(timeout=60)

    merged = merge_files([str(client_trace), str(server_trace)],
                         str(out / "merged.json"))
    print(summarize(merged))

    events = merged["traceEvents"]
    pids = {e["pid"] for e in events if "pid" in e}
    assert len(pids) == 2, f"expected 2 process tracks, got {pids}"
    paired = check_flow_pairing(merged)
    assert paired > 0, "no cross-process flow arrows paired"
    # the server handler spans carry the client link by hex span id
    client_spans = {e["args"]["span_id"] for e in events
                    if e.get("ph") == "X"
                    and e["name"].startswith("ps_client_")}
    rpc = [e for e in events if e.get("ph") == "X"
           and e["name"] == "ps_rpc"]
    assert rpc, "no ps_rpc handler spans in the server trace"
    for e in rpc:
        assert e["args"]["link_span"] in client_spans, e
    print(f"paired flow arrows: {paired}; "
          f"linked ps_rpc handler spans: {len(rpc)}")
    device_alignment_case(out_dir)
    print("smoke: ok")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("traces", nargs="*",
                    help="per-process Chrome trace JSON files")
    ap.add_argument("--out", default=None,
                    help="write the merged trace here")
    ap.add_argument("--smoke", action="store_true",
                    help="two-process merge proof (tier-1 mode)")
    ap.add_argument("--out-dir", default=None,
                    help="--smoke artifact directory (temp default)")
    ap.add_argument("--serve", default=None, metavar="TRACE_OUT",
                    help=argparse.SUPPRESS)  # internal child mode
    args = ap.parse_args()

    if args.serve:
        serve(args.serve)
        return
    if args.smoke:
        smoke(args.out_dir or tempfile.mkdtemp(prefix="dkt_trace_"))
        return
    if not args.traces or not args.out:
        ap.error("merge mode needs trace files and --out "
                 "(or pass --smoke)")
    merged = merge_files(args.traces, args.out)
    print(summarize(merged))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
