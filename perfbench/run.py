"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data that ``BENCHMARK.json`` names: its
configuration (``perfbench/configs/<config>.json``, whose ``arch`` names
the weights, the plain reference, the counts and the adapter), its
traffic (``perfbench/traffic/<traffic>.json``, whose ``kind`` names the
driver in ``perfbench/kinds/``), its limits
(``perfbench/limits/<cell>.json``) and, for ``--trace 1``, one reader for
each per-layer metric (``perfbench/metrics/<metric>.py``).  A later PR
adds a cell by adding such files and one entry; see the README.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def load_manifest(repo: str = REPO) -> dict:
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} {name!r}")


def load_config(manifest: dict, name: str, repo: str = REPO) -> dict:
    entry = find(manifest["configs"], name, "configuration")
    with open(os.path.join(repo, entry["file"])) as f:
        cfg = json.load(f)
    cfg["n_layer_published"] = cfg.get("published", {}).get(
        "n_layer", cfg.get("n_layer"))
    return cfg


def load_arch(arch: str):
    """(adapter, reference, counts, weights) of an architecture."""
    return tuple(importlib.import_module(f"perfbench.{part}.{arch}")
                 for part in ("adapters", "reference", "counts", "weights"))


def load_json(repo: str, *parts) -> dict:
    with open(os.path.join(repo, *parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def require_chips(chips: int):
    """The TPU the cell asks for, with a row in the table of peaks, or no
    result at all."""
    import jax
    from perfbench import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX reports {devices[0].platform!r};"
                         " the benchmark measures the chip and nothing else")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX reports "
                         f"{len(devices)}")
    return peaks.peaks_for(devices[0].device_kind)


def make_context(manifest, cell, args, peaks, repo=REPO):
    cfg = load_config(manifest, cell["config"], repo)
    from perfbench import trafficgen

    traffic = trafficgen.load(cell["traffic"], repo)
    limits = load_json(repo, "perfbench", "limits", f"{cell['name']}.json")
    trace_dir = os.path.join(repo, ".perfbench_trace", cell["name"])
    return types.SimpleNamespace(
        cell=cell, config=cfg, traffic=traffic, limits=limits["limits"],
        arch=load_arch(cfg["arch"]), seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), trace_dir=trace_dir, chips=cell["chips"],
        peaks=peaks, t_process_start=T_PROCESS_START)


def result_line(manifest, cell, ctx, out) -> dict:
    """The JSON object of the run's last line."""
    from perfbench import common

    device = {**common.device_record(),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["checks"].correct, "attempted": out["attempted"],
            "failed": out["failed"]}
    metrics = {}
    if not ctx.trace:
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    else:
        from perfbench import trace as trace_mod

        lines = trace_mod.read_xplane(ctx.trace_dir)
        busy = trace_mod.busy(lines)
        layers = types.SimpleNamespace(
            lines=lines, trace=trace_mod, busy=busy, ctx=ctx, **out["layers"])
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = importlib.import_module(f"perfbench.metrics.{m['name']}")
            value = reader.read(layers)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        line["breakdown"] = {
            "device_ops": trace_mod.top_device_ops(lines),
            "idle_gaps": trace_mod.idle_by_host_activity(lines)}
    line["metrics"] = metrics
    line["device"] = device
    line["window"] = {k: v for k, v in out["end_to_end"].items()
                      if k not in metrics}
    line["stages"] = out["stages"]
    line["checks"] = out["checks"].rows
    return line


def place_compile_cache() -> None:
    """JAX's persistent cache where the program puts it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), and
    every program kept, however quickly it compiled."""
    import jax
    from distkeras_tpu import profiling

    profiling.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run_cell(workload: str, seed: int, seconds: float, trace: int,
             peaks: dict, repo: str = REPO) -> dict:
    """Everything of a run after the look for a chip: the tests drive this
    on the CPU, with the table's row handed in."""
    manifest = load_manifest(repo)
    cell = find(manifest["workloads"], workload, "workload")
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    ctx = make_context(manifest, cell, args, peaks, repo)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    kind = importlib.import_module(f"perfbench.kinds.{ctx.traffic['kind']}")
    out = kind.run(ctx)
    line = result_line(manifest, cell, ctx, out)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    for text in out["checks"].lines():
        print(text, file=sys.stderr)
    sys.stderr.flush()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load_manifest()
    cell = find(manifest["workloads"], args.workload, "workload")

    place_compile_cache()
    peaks = require_chips(cell["chips"])
    line = run_cell(args.workload, args.seed, args.seconds, args.trace, peaks)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
