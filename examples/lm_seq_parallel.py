"""Long-context LM training with sequence parallelism (ring attention).

Beyond the reference (SURVEY.md §5: it has no long-sequence story): the
time axis is sharded across the mesh, each device holds T/N positions,
and ring attention exchanges K/V blocks over the ring — the same
parameters and losses as dense single-device training (parity-tested in
tests/test_ring_attention.py), at O(T/N) memory per device.

Run:  python examples/lm_seq_parallel.py --devices 8
      python examples/lm_seq_parallel.py --devices 8 --seq-len 512
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _common import (add_data_option, load_dataset,
                     make_parser, parse_args_and_setup, report)


def main():
    parser = make_parser(__doc__, rows=512, epochs=4, batch_size=16,
                         learning_rate=3e-3)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--vocab-size", type=int, default=64)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--q-chunk", type=int, default=None,
                        help="within-device q block length for ring "
                             "attention (bounds transient memory to "
                             "[q_chunk, T_local] per hop)")
    parser.add_argument("--impl", choices=["xla", "flash"],
                        default="xla",
                        help="'flash': run each ring hop through the "
                             "Pallas hop kernels (ops.attention; "
                             "PERF.md §17 addendum 2)")
    add_data_option(parser)
    args = parse_args_and_setup(parser)
    from distkeras_tpu.profiling import profiler_trace

    with profiler_trace(args.profile_dir):
        _run(args)


def _run(args):
    import time

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.ops.losses import resolve_loss

    n_dev = len(jax.devices())
    if args.seq_len % n_dev:
        raise SystemExit(f"--seq-len {args.seq_len} must divide by the "
                         f"{n_dev} devices")
    t_local = args.seq_len // n_dev
    if args.q_chunk and args.q_chunk < t_local \
            and t_local % args.q_chunk:
        raise SystemExit(
            f"--q-chunk {args.q_chunk} must divide the per-device "
            f"sequence length {t_local}")
    mesh = Mesh(np.asarray(jax.devices()), ("seq",))

    data = load_dataset(
        args, lambda: datasets.lm_synth(args.rows,
                                        seq_len=args.seq_len,
                                        vocab_size=args.vocab_size,
                                        seed=args.seed))
    rows = len(data)
    lm_cfg = dict(vocab_size=args.vocab_size, num_layers=args.layers,
                  d_model=args.d_model, num_heads=4,
                  max_len=args.seq_len, dtype="float32")
    seq_model = ModelSpec.from_config(model_config(
        "transformer_lm", (args.seq_len,), input_dtype="int32",
        seq_axis="seq", attn_q_chunk=args.q_chunk, **lm_cfg)).build()
    if args.impl == "flash":
        from distkeras_tpu.parallel.ring_attention import ring_attn_fn

        # --q-chunk maps to the kernel's q block size here (the XLA
        # impl's q_chunk arg does not apply to the flash path)
        seq_model = seq_model.clone(attn_fn=ring_attn_fn(
            "seq", impl="flash", block_q=args.q_chunk,
            block_k=args.q_chunk))
    dense_spec = ModelSpec.from_config(model_config(
        "transformer_lm", (args.seq_len,), input_dtype="int32",
        **lm_cfg))

    tokens = data["features"][:args.batch_size]
    variables = dense_spec.build().init(jax.random.key(args.seed),
                                        tokens)
    tx = optax.adam(args.learning_rate)
    opt_state = tx.init(variables["params"])
    loss_fn = resolve_loss("sparse_categorical_crossentropy")

    def shard_loss(vs, toks, tgt):
        return jax.lax.pmean(
            loss_fn(seq_model.apply(vs, toks), tgt), "seq")

    sharded = jax.shard_map(
        shard_loss, mesh=mesh,
        in_specs=(P(), P(None, "seq"), P(None, "seq")), out_specs=P(),
        # the Pallas interpreter requires check_vma=False (JAX
        # limitation; see parallel.ring_attention docs)
        check_vma=args.impl != "flash")

    @jax.jit
    def step(vs, opt_state, toks, tgt):
        loss, g = jax.value_and_grad(
            lambda p: sharded({**vs, "params": p}, toks, tgt))(
                vs["params"])
        upd, opt_state = tx.update(g, opt_state)
        return ({**vs, "params": optax.apply_updates(vs["params"],
                                                     upd)},
                opt_state, loss)

    start = time.time()
    epoch_losses = []
    steps_per_epoch = rows // args.batch_size
    if not steps_per_epoch:
        raise SystemExit(f"--rows {rows} < --batch-size "
                         f"{args.batch_size}: no full batch to train on")
    for epoch in range(args.epochs):
        order = np.random.default_rng(args.seed + epoch).permutation(
            rows)
        losses = []
        for s in range(steps_per_epoch):
            idx = order[s * args.batch_size:(s + 1) * args.batch_size]
            variables, opt_state, loss = step(
                variables, opt_state, data["features"][idx],
                data["label"][idx])
            losses.append(float(loss))
        epoch_losses.append(float(np.mean(losses)))
        print(f"[lm_seq_parallel] epoch {epoch}: "
              f"loss {epoch_losses[-1]:.4f}")

    class _T:  # report() duck-type
        training_time = time.time() - start
        history = {"epoch_loss": epoch_losses}

    report("lm_seq_parallel", _T, {"final_loss": epoch_losses[-1]},
           seq_len=args.seq_len, devices=n_dev)


if __name__ == "__main__":
    main()
