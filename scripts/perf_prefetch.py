"""IO/compute overlap A/B for the out-of-core path (VERDICT r3 #2).

Measures epoch wall-time of training over ``.npz`` shard files with the
one-deep segment prefetch disabled vs enabled
(``DKT_SEGMENT_PREFETCH=0|1``), plus the raw ingredients — pure segment
IO (load+shuffle) and pure device compute — so the table can say not
just "what changed" but "what bound the epoch".

Protocol: each arm trains ``1`` epoch and then ``1 + N`` epochs with a
fresh trainer; the difference is N steady-state epochs with the jit
compile and other fixed costs cancelled.  Results are appended to
stdout as one JSON line per arm; PERF.md carries the table.

Run on the TPU from the repo root:
    python scripts/perf_prefetch.py --trainer single
    python scripts/perf_prefetch.py --trainer adag
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trainer", choices=["single", "adag"],
                    default="single")
    ap.add_argument("--format", choices=["npz", "csv"], default="npz",
                    help="npz: ResNet-18 over image shards (host IO is "
                         "binary reads — cheap).  csv: Wide&Deep over "
                         "Criteo-shaped text shards with a per-shard "
                         "ETL map (parse + hash-bucket + assemble — "
                         "the host-heavy ingestion path)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--image", type=int, default=64)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--epochs", type=int, default=3,
                    help="steady-state epochs measured (on top of the "
                         "1-epoch warm arm)")
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args()

    from distkeras_tpu.data import (Dataset, ShardedDataset, datasets,
                                    transformers as tf)
    from distkeras_tpu.models import model_config
    from distkeras_tpu.trainers import ADAG, SingleTrainer

    tmp = tempfile.mkdtemp(prefix="dkt_prefetch_")
    if args.format == "npz":
        rows = args.rows or 4096
        full = datasets.synthetic_classification(
            rows, (args.image, args.image, 3), 100, seed=0)
        paths = full.to_npz_shards(os.path.join(tmp, "part"),
                                   rows_per_shard=rows // args.shards)
        sd = ShardedDataset(paths)
        # ResNet-18 (basic blocks 2-2-2-2) at the shard scale the
        # rig's host RAM supports; bf16 + group norm, the flagship's
        # settings.
        cfg = model_config("resnet", (args.image, args.image, 3),
                           num_classes=100, stage_sizes=(2, 2, 2, 2),
                           bottleneck=False, width=64)
    else:
        rows = args.rows or 65536
        num_dense, num_cat, buckets = 13, 26, 1000
        full = datasets.criteo_synth(rows, num_dense=num_dense,
                                     num_categorical=num_cat,
                                     vocab_size=5000, seed=0)
        dense = full["dense"]
        per = rows // args.shards
        paths = []
        header = (",".join(f"d{j}" for j in range(num_dense))
                  + "," + ",".join(f"c{j}" for j in range(num_cat))
                  + ",label")
        for s in range(args.shards):
            p = os.path.join(tmp, f"part-{s:05d}.csv")
            with open(p, "w") as fh:
                fh.write(header + "\n")
                for i in range(s * per, (s + 1) * per):
                    fh.write(",".join(
                        [f"{dense[i, j]:.6g}" for j in range(num_dense)]
                        + [str(full[f"c{j}"][i]) for j in range(num_cat)]
                        + [str(full["label"][i])]) + "\n")
            paths.append(p)
        etl = tf.Pipeline(
            [tf.HashBucketTransformer(f"c{j}", buckets)
             for j in range(num_cat)]
            + [tf.AssembleTransformer(
                [f"d{j}" for j in range(num_dense)]
                + [f"c{j}_bucket" for j in range(num_cat)])])
        base = Dataset.from_csv_shards(os.path.join(tmp, "part-*.csv"))
        etl.fit(base.load_shard(0))
        sd = base.map(etl.transform)
        cfg = model_config("wide_deep", (num_dense + num_cat,),
                           num_dense=num_dense,
                           num_categorical=num_cat,
                           vocab_size=buckets, num_classes=2)
    shard_mb = os.path.getsize(paths[0]) / 1e6

    def build():
        if args.trainer == "single":
            return SingleTrainer(cfg, batch_size=args.batch,
                                 learning_rate=0.1, seed=0)
        return ADAG(cfg, num_workers=args.workers,
                    communication_window=2,
                    batch_size=args.batch // args.workers,
                    learning_rate=0.1, seed=0)

    def timed_train(num_epoch: int):
        t = build()
        t.num_epoch = num_epoch
        start = time.monotonic()
        t.train(sd)
        wall = time.monotonic() - start
        # exact consumer-side blocked-on-segment seconds (recorded per
        # epoch by the trainers) — the noise-free counterpart of the
        # wall-clock A/B
        stalls = t.history.get("segment_stall_s", [])
        return wall, (sum(stalls[1:]) / max(len(stalls) - 1, 1)
                      if len(stalls) > 1 else (stalls or [0.0])[-1])

    # throwaway warmup: the very first train pays the device compile;
    # everything timed below reuses the in-process XLA compile cache
    os.environ["DKT_SEGMENT_PREFETCH"] = "0"
    timed_train(1)

    # pure segment IO: what one epoch's loads+shuffles cost with no
    # training at all (the stall an overlapped epoch can hide)
    io_start = time.monotonic()
    for seg in sd.epoch_segments(seed=0):
        pass
    io_epoch = time.monotonic() - io_start

    out = {"trainer": args.trainer, "format": args.format, "rows": rows,
           "image": args.image, "shards": args.shards,
           "shard_mb": round(shard_mb, 1), "batch": args.batch,
           "steady_epochs": args.epochs,
           "io_epoch_s": round(io_epoch, 3)}
    for setting in ("0", "1"):
        os.environ["DKT_SEGMENT_PREFETCH"] = setting
        warm, _ = timed_train(1)
        long, stall = timed_train(1 + args.epochs)
        per_epoch = (long - warm) / args.epochs
        out[f"epoch_s_prefetch_{setting}"] = round(per_epoch, 3)
        out[f"total_1ep_s_prefetch_{setting}"] = round(warm, 3)
        out[f"stall_s_prefetch_{setting}"] = round(stall, 3)
    saved = out["epoch_s_prefetch_0"] - out["epoch_s_prefetch_1"]
    out["saved_s_per_epoch"] = round(saved, 3)
    out["saved_pct"] = round(100 * saved / out["epoch_s_prefetch_0"], 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
