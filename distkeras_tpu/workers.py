"""Worker machinery: the on-chip training loop.

TPU-native redesign of the reference's ``distkeras/workers.py`` (SURVEY.md
§3.2): where the reference's worker is a Python closure shipped into a
Spark task that calls ``model.train_on_batch`` and crosses the Python ↔
backend boundary *every step*, the rebuild's worker is a jitted
``train_step`` scanned over a window of batches — the whole communication
window executes on-device in one XLA program (the hot-loop fix called out
in SURVEY.md §3.2 observations).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import optax
from flax import struct

from distkeras_tpu.ops.losses import resolve_loss

Pytree = Any


# ---------------------------------------------------------------------------
# Optimizers, resolvable by Keras-style names (reference workers compile the
# model with a `worker_optimizer` string — SURVEY.md §2.1 Worker base).
# ---------------------------------------------------------------------------

OPTIMIZERS: dict[str, Callable[..., optax.GradientTransformation]] = {
    "sgd": lambda lr=0.01, **kw: optax.sgd(lr, **kw),
    "momentum": lambda lr=0.01, m=0.9, **kw: optax.sgd(lr, momentum=m, **kw),
    "nesterov": lambda lr=0.01, m=0.9, **kw: optax.sgd(
        lr, momentum=m, nesterov=True, **kw),
    "adam": lambda lr=0.001, **kw: optax.adam(lr, **kw),
    "adagrad": lambda lr=0.01, **kw: optax.adagrad(lr, **kw),
    "rmsprop": lambda lr=0.001, **kw: optax.rmsprop(lr, **kw),
    "adamw": lambda lr=0.001, **kw: optax.adamw(lr, **kw),
}


SCHEDULES: dict[str, Callable[..., Any]] = {
    "constant": lambda value: optax.constant_schedule(value),
    "cosine": optax.cosine_decay_schedule,
    "exponential": optax.exponential_decay,
    "warmup_cosine": optax.warmup_cosine_decay_schedule,
    "piecewise_constant": lambda init_value, boundaries_and_scales:
        optax.piecewise_constant_schedule(
            init_value, {int(k): float(v)
                         for k, v in boundaries_and_scales.items()}),
}


def resolve_schedule(spec):
    """Learning-rate spec -> something optax accepts as a rate.

    ``spec`` may be a float (constant), a callable (an optax schedule,
    passed through), or a JSON-friendly dict
    ``{"schedule": <name>, **kwargs}`` with optax's own kwarg names —
    e.g. ``{"schedule": "cosine", "init_value": 0.1,
    "decay_steps": 1000}``.  Schedules advance with the optimizer's
    update count: per-worker local steps under the PS trainers, global
    steps under Single/Sync.
    """
    import numbers

    if spec is None or isinstance(spec, numbers.Real) or callable(spec):
        return spec  # numbers.Real covers numpy scalar types too
    if hasattr(spec, "dtype") and getattr(spec, "ndim", None) == 0:
        return spec  # 0-d array scalar — optax takes it directly
    if isinstance(spec, Mapping):
        kwargs = dict(spec)
        name = kwargs.pop("schedule", None)
        if name not in SCHEDULES:
            raise KeyError(f"unknown schedule {name!r}; known: "
                           f"{sorted(SCHEDULES)}")
        return SCHEDULES[name](**kwargs)
    raise TypeError(f"cannot resolve a learning rate from {type(spec)}")


def resolve_optimizer(optimizer, learning_rate=None,
                      **kwargs) -> optax.GradientTransformation:
    """String name / optax transform -> optax transform.
    ``learning_rate`` accepts anything ``resolve_schedule`` does."""
    if isinstance(optimizer, optax.GradientTransformation):
        return optimizer
    if isinstance(optimizer, str):
        if optimizer not in OPTIMIZERS:
            raise KeyError(f"unknown optimizer {optimizer!r}; known: "
                           f"{sorted(OPTIMIZERS)}")
        if learning_rate is not None:
            kwargs["lr"] = resolve_schedule(learning_rate)
        return OPTIMIZERS[optimizer](**kwargs)
    raise TypeError(f"cannot resolve optimizer from {type(optimizer)}")


# ---------------------------------------------------------------------------
# Train state.
# ---------------------------------------------------------------------------


class TrainState(struct.PyTreeNode):
    """Per-worker training state.

    ``model_state`` carries non-parameter collections (e.g. BatchNorm
    ``batch_stats``); it stays worker-local under the PS trainers —
    parameter-server rules exchange ``params`` only (SURVEY.md §7 L1).
    """

    step: jnp.ndarray
    params: Pytree
    opt_state: Pytree
    model_state: Mapping[str, Pytree]
    rng: jax.Array

    @classmethod
    def create(cls, variables: Mapping[str, Pytree],
               tx: optax.GradientTransformation,
               rng: jax.Array) -> "TrainState":
        params = variables["params"]
        model_state = {k: v for k, v in variables.items() if k != "params"}
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=tx.init(params), model_state=model_state,
                   rng=rng)

    def variables(self) -> dict[str, Pytree]:
        return {"params": self.params, **self.model_state}


# ---------------------------------------------------------------------------
# Jitted step + window runner.
# ---------------------------------------------------------------------------


def make_train_step(model, loss, tx: optax.GradientTransformation,
                    features_col: str = "features",
                    label_col: str = "label"):
    """Build ``step(state, batch) -> (state, metrics)``.

    Handles dropout rngs and mutable collections (batch_stats) generically;
    pure and jittable, so it can be ``vmap``-ed per worker and ``scan``-ed
    over a communication window.

    MULTI-OUTPUT models (tuple forward — e.g. an ingested two-head
    keras DAG): pass ``loss`` as a sequence of per-head losses and
    ``label_col`` as the matching sequence of label columns; the
    objective is their sum (plus any sown auxiliary losses).
    """
    multi = isinstance(loss, (list, tuple))
    if multi != isinstance(label_col, (list, tuple)):
        raise ValueError(
            "loss and label_col must both be sequences (one per "
            "output head) or both single values; got "
            f"loss={loss!r}, label_col={label_col!r}")
    if multi:
        if len(loss) != len(label_col):
            raise ValueError(
                f"{len(loss)} losses vs {len(label_col)} label "
                f"columns — one of each per output head")
        head_fns = [resolve_loss(l) for l in loss]

        def loss_fn(logits, ys):
            if not (isinstance(logits, tuple)
                    and len(logits) == len(head_fns)):
                raise ValueError(
                    f"model produced "
                    f"{len(logits) if isinstance(logits, tuple) else 1}"
                    f" output head(s) but {len(head_fns)} losses were "
                    f"configured")
            total = jnp.float32(0.0)
            for fn, lg, y in zip(head_fns, logits, ys):
                total = total + fn(lg, y)
            return total
    else:
        single_fn = resolve_loss(loss)

        def loss_fn(logits, y):
            if isinstance(logits, tuple):
                raise ValueError(
                    "multi-output model needs a sequence of losses "
                    "and label columns (one per head); got a single "
                    "loss")
            return single_fn(logits, y)

    def step(state: TrainState, batch: Mapping[str, jnp.ndarray]):
        x = batch[features_col]
        y = (tuple(batch[c] for c in label_col) if multi
             else batch[label_col])
        rng = jax.random.fold_in(state.rng, state.step)
        # "losses" is ALWAYS mutable — auxiliary objectives sown by
        # modules (e.g. the MoE load-balance loss) must reach the
        # objective even when the caller built the state from
        # params-only variables (no init-time "losses" entry), or they
        # would be dropped silently.
        carried_keys = list(state.model_state)
        mutable_keys = carried_keys + (
            [] if "losses" in carried_keys else ["losses"])

        def objective(params):
            # "losses" is stripped from the INPUT so each apply sows a
            # fresh, shape-stable collection — flax sow would otherwise
            # append to the carried tuples every step, breaking the
            # scan carry.
            model_state_in = {k: v for k, v in state.model_state.items()
                              if k != "losses"}
            variables = {"params": params, **model_state_in}
            with jax.named_scope("forward_loss"):
                logits, new_model_state = model.apply(
                    variables, x, train=True, rngs={"dropout": rng},
                    mutable=mutable_keys)
                new_model_state = dict(new_model_state)
                aux_sum = jnp.float32(0.0)
                for leaf in jax.tree_util.tree_leaves(
                        new_model_state.get("losses", {})):
                    aux_sum = aux_sum + leaf
                if "losses" not in carried_keys:
                    # keep the carry's structure identical to the input
                    # state (scan requires it)
                    new_model_state.pop("losses", None)
                task_loss = loss_fn(logits, y)
            return task_loss + aux_sum, (task_loss, aux_sum,
                                         new_model_state)

        # the transposed ops inherit the scope they are made under, so
        # a profile reads .../backward/transpose(jvp(forward_loss))/...
        with jax.named_scope("backward"):
            ((loss_val, (task_loss, aux_sum, new_model_state)),
             grads) = jax.value_and_grad(
                objective, has_aux=True)(state.params)
        with jax.named_scope("optimizer_update"):
            updates, new_opt_state = tx.update(grads, state.opt_state,
                                               state.params)
            new_params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt_state,
                                  model_state=new_model_state)
        # "loss" stays the task loss (comparable with eval loss and
        # aux-free runs); the auxiliary sum is reported separately.
        metrics = {"loss": task_loss, "aux_loss": aux_sum,
                   "grad_norm": grad_norm}
        return new_state, metrics

    return step


def make_window_runner(step_fn):
    """``run(state, batches) -> (state, metrics)``: lax.scan ``step_fn``
    over a stacked window of batches (leaves ``[window, B, ...]``).  This
    is the reference's per-window inner loop compiled into one XLA program.
    """

    def run(state: TrainState, batches: Mapping[str, jnp.ndarray]):
        return jax.lax.scan(step_fn, state, batches)

    return run


def make_eval_step(model, loss, features_col: str = "features",
                   label_col: str = "label"):
    """Build ``eval_step(variables, batch) -> metrics`` (no mutation)."""
    loss_fn = resolve_loss(loss)

    @functools.partial(jax.jit, static_argnums=())
    def eval_step(variables, batch):
        logits = model.apply(variables, batch[features_col], train=False)
        return {"loss": loss_fn(logits, batch[label_col]),
                "logits": logits}

    return eval_step
