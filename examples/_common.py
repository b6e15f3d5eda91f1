"""Shared plumbing for the example scripts.

The reference's user surface was example notebooks running the full
ETL -> train -> predict -> evaluate pipeline on a local Spark context
(SURVEY.md §1 L7, §4 "example notebooks as integration tests").  These
scripts are the rebuild's equivalent: each one is a runnable pipeline for
one BASELINE.md config, defaulting to small learnable synthetic data
(zero egress — see distkeras_tpu.data.datasets) and shapes that finish in
seconds on a laptop CPU or a single TPU chip.

``--devices N`` is the Spark ``local[N]`` analogue: it forces an
N-device virtual CPU mesh so the distributed trainers exercise real
mesh sharding + ICI-style collectives without N chips.  It must take
effect before jax initializes, hence ``parse_args_and_setup`` must be
called before importing anything that imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# Make the examples runnable from a source checkout without installation.
_REPO_ROOT = str(Path(__file__).resolve().parent.parent)
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def make_parser(description: str, **defaults) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--rows", type=int,
                   default=defaults.get("rows", 2048),
                   help="synthetic dataset rows")
    p.add_argument("--epochs", type=int,
                   default=defaults.get("epochs", 3))
    p.add_argument("--batch-size", type=int,
                   default=defaults.get("batch_size", 32),
                   help="per-worker batch size")
    p.add_argument("--workers", type=int,
                   default=defaults.get("workers", 4),
                   help="data-parallel workers (mesh axis size)")
    p.add_argument("--window", type=int,
                   default=defaults.get("window", 2),
                   help="communication window (local steps per commit)")
    p.add_argument("--learning-rate", type=float,
                   default=defaults.get("learning_rate", 0.01))
    p.add_argument("--devices", type=int, default=0, metavar="N",
                   help="force an N-device virtual CPU mesh (the "
                        "reference's local[N]; 0 = use real devices)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="write checkpoints here (enables --resume)")
    p.add_argument("--resume", default=None, metavar="DIR",
                   help="resume from a checkpoint directory")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the training "
                        "run there (view with TensorBoard)")
    p.add_argument("--seed", type=int, default=0)
    return p


def add_data_option(p: argparse.ArgumentParser,
                    required=("features", "label")):
    """Opt-in ``--data-npz`` for scripts that honor it via
    ``load_dataset`` (only those — a flag every script parses but most
    ignore would silently train on synthetic data).  ``required`` names
    the archive columns, single-sourced: it feeds both the help text
    and ``load_dataset``'s validation (via a parser default)."""
    p.set_defaults(_npz_required=tuple(required))
    p.add_argument("--data-npz", default=None, metavar="FILE",
                   help="train on real data from an .npz archive "
                        "instead of synthetic: each array becomes a "
                        f"Dataset column (needs {list(required)})")
    return p


def load_dataset(args, synth_fn, required=None, shuffle_seed=None):
    """The example's dataset: ``--data-npz FILE`` (real data, no egress
    needed — any locally produced archive works) or the config's
    synthetic fallback ``synth_fn()``.  Real archives are shuffled
    (seeded) so ordered rows — e.g. grouped by class — don't skew
    contiguous train/holdout splits.  ``required`` defaults to what
    ``add_data_option`` registered; pass it explicitly only when the
    real requirement depends on other args."""
    if args.data_npz is None:
        return synth_fn()
    if required is None:
        required = getattr(args, "_npz_required",
                           ("features", "label"))
    import numpy as np

    from distkeras_tpu.data.dataset import Dataset

    with np.load(args.data_npz) as archive:
        columns = {k: np.asarray(archive[k]) for k in archive.files}
    missing = [c for c in required if c not in columns]
    if missing:
        raise SystemExit(
            f"--data-npz {args.data_npz}: missing required "
            f"column(s) {missing}; found {sorted(columns)}")
    print(f"[data] loaded {args.data_npz}: "
          + ", ".join(f"{k}{tuple(v.shape)}"
                      for k, v in sorted(columns.items())))
    return Dataset(columns).shuffle(
        seed=args.seed if shuffle_seed is None else shuffle_seed)


def parse_args_and_setup(parser: argparse.ArgumentParser):
    """Parse args and, if requested, force a virtual CPU mesh.

    Must run before any jax *backend* is initialized (first device use),
    which holds as long as it is called before distkeras_tpu imports —
    XLA_FLAGS are read at backend init, and the platform pin is a
    jax.config update (same recipe as ``__graft_entry__._force_cpu_mesh``).
    """
    args = parser.parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
        n = len(jax.devices())
        if n != args.devices:
            raise RuntimeError(
                f"--devices {args.devices} requested but the jax backend "
                f"was already initialized with {n} devices")
    return args


def report(config_name: str, trainer, metrics: dict, **extra) -> None:
    """Print the run summary: human-readable lines + one JSON line."""
    print(f"[{config_name}] trained in {trainer.training_time:.2f}s")
    losses = trainer.history.get("epoch_loss", [])
    if losses:
        print(f"[{config_name}] epoch loss: "
              + " -> ".join(f"{x:.4f}" for x in losses))
    for k, v in metrics.items():
        print(f"[{config_name}] {k}: {v:.4f}")
    summary = {
        "config": config_name,
        "training_time_s": round(trainer.training_time, 3),
        "epoch_loss": [round(float(x), 5) for x in losses],
        **{k: round(float(v), 5) for k, v in metrics.items()},
        **extra,
    }
    print(json.dumps(summary))


def timed(label: str):
    """Context manager printing wall time of a pipeline stage."""

    class _Timer:
        def __enter__(self):
            self.t0 = time.time()
            return self

        def __exit__(self, *exc):
            print(f"[{label}] {time.time() - self.t0:.2f}s")

    return _Timer()


def resolve_platform_defaults(args, **tiers):
    """Fill ``None``-defaulted size knobs per backend: each kwarg is
    ``attr=(cpu_value, other_value)``.  Conv demos need smaller CPU
    sizes — XLA:CPU lowers the PS round's batched-parameter convs
    through a very slow grouped-conv path, while the same program is
    faster than sequential stepping on TPU (PERF.md §10).  Call after
    ``parse_args_and_setup`` (the backend pin must land first)."""
    import jax

    on_cpu = jax.default_backend() == "cpu"
    for name, (cpu_value, other_value) in tiers.items():
        if getattr(args, name) is None:
            setattr(args, name, cpu_value if on_cpu else other_value)
