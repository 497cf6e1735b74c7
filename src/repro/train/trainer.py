"""Training loop: microbatched grad accumulation, mixed precision, ZeRO-1.

``make_train_step`` builds the pure step function used both by the real
trainer (examples/) and by the multi-pod dry-run (launch/dryrun.py).  The
sharding story:

* batch sharded over DP axes ``(pod, data)``; params Megatron-TP over
  ``model`` (see ``models.transformer.param_pspecs``);
* grads are accumulated in ``grad_dtype`` (fp32 default; bf16 halves the
  gradient all-reduce bytes — the gradient-compression knob);
* optimizer moments optionally ZeRO-1-sharded over DP
  (``optim.zero1_specs``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs.base import ArchConfig
from repro.models import transformer as T


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    zero1: bool = True
    grad_dtype: Any = jnp.float32       # bf16 = compressed grad all-reduce
    compute_dtype: Any = jnp.bfloat16
    adamw: optim.AdamWConfig = field(default_factory=optim.AdamWConfig)


def cast_for_compute(params, dtype):
    """Cast >=2D floating params to the compute dtype (norms stay fp32)."""
    def cast(a):
        if a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dtype)
        return a
    return jax.tree.map(cast, params)


def make_train_step(cfg: ArchConfig, ctx: T.ParallelCtx, tcfg: TrainConfig,
                    has_frontend: bool = False):
    """Returns step(params, opt_state, tokens, labels[, frontend])."""

    def loss_fn(params_c, tokens, labels, frontend):
        return T.lm_loss(params_c, tokens, labels, cfg, ctx,
                         frontend=frontend)

    def step(params, opt_state, tokens, labels, frontend=None):
        # batches arrive microbatch-major: (n_micro, mb, ...) so the
        # accumulation scan slices along an UNSHARDED axis (a traced
        # dynamic_slice over the data-sharded batch dim would force GSPMD
        # to all-gather the whole batch — fatal for VLM frontends)
        n_micro = tokens.shape[0]
        assert n_micro == tcfg.microbatches, (n_micro, tcfg.microbatches)

        params_c = cast_for_compute(params, tcfg.compute_dtype)

        def micro(carry, xs):
            gacc, lacc = carry
            if has_frontend:
                t, l, fe = xs
                # stub modality input: block its (unused) cotangent, which
                # would otherwise materialize fp32 at full stacked size
                fe = jax.lax.stop_gradient(fe)
            else:
                (t, l), fe = xs, None
            loss, grads = jax.value_and_grad(loss_fn)(params_c, t, l, fe)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(tcfg.grad_dtype), gacc, grads)
            return (gacc, lacc + loss), None

        gacc0 = jax.tree.map(
            lambda a: jnp.zeros(a.shape, tcfg.grad_dtype), params)
        xs = (tokens, labels, frontend) if has_frontend else (tokens, labels)
        (gacc, loss_sum), _ = jax.lax.scan(
            micro, (gacc0, jnp.zeros((), jnp.float32)), xs)
        grads = jax.tree.map(lambda g: g / n_micro, gacc)
        loss = loss_sum / n_micro

        new_params, new_opt, metrics = optim.update(
            tcfg.adamw, params, grads, opt_state)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return step


def make_shardings(cfg: ArchConfig, ctx: T.ParallelCtx, tcfg: TrainConfig,
                   params_shape, has_frontend: bool = False):
    """(in_shardings, out_shardings) trees for jit(train_step)."""
    mesh = ctx.mesh
    ns = lambda spec: NamedSharding(mesh, spec)
    pspecs = T.param_pspecs(params_shape, cfg,
                            model_size=mesh.shape[ctx.model_axis])
    p_shard = jax.tree.map(lambda s: ns(s), pspecs,
                           is_leaf=lambda s: isinstance(s, P))
    if tcfg.zero1:
        mspecs = optim.zero1_specs(pspecs, params_shape, ctx.dp_axes,
                                   ctx.dp_size())
    else:
        mspecs = pspecs
    m_shard = jax.tree.map(lambda s: ns(s), mspecs,
                           is_leaf=lambda s: isinstance(s, P))
    opt_shard = optim.AdamWState(ns(P()), m_shard, m_shard)
    batch_shard = ns(P(None, ctx.dp, None))        # (n_micro, mb, seq)
    ins = [p_shard, opt_shard, batch_shard, batch_shard]
    if has_frontend:
        ins.append(ns(P(None, ctx.dp, None, None)))
    metrics_shard = {"lr": ns(P()), "grad_norm": ns(P()), "loss": ns(P())}
    outs = (p_shard, opt_shard, metrics_shard)
    return tuple(ins), outs


def fit(params, cfg: ArchConfig, ctx: T.ParallelCtx, tcfg: TrainConfig,
        dataset, n_steps: int, log_every: int = 10, callback=None):
    """Simple single-host fit loop (examples / integration tests).  With
    ``ctx.mesh`` the step, params and optimizer state are sharded over it
    (``make_shardings``): params are moved to their shardings (a no-op for
    params built there, as ``launch/train.py`` does) and the optimizer
    moments are made sharded, never whole on one device.  Without a mesh
    everything stays on one device."""
    step = make_train_step(cfg, ctx, tcfg)
    if ctx.mesh is None:
        step_fn = jax.jit(step)
        opt_state = optim.init(params)
    else:
        ins, outs = make_shardings(cfg, ctx, tcfg,
                                   jax.eval_shape(lambda: params))
        step_fn = jax.jit(step, in_shardings=ins, out_shardings=outs)
        params = jax.device_put(params, ins[0])
        opt_state = jax.jit(optim.init, out_shardings=ins[1])(params)
    history = []
    n_micro = tcfg.microbatches
    for i, (tokens, labels) in zip(range(n_steps), dataset):
        tokens = jnp.asarray(tokens).reshape((n_micro, -1) + tokens.shape[1:])
        labels = jnp.asarray(labels).reshape((n_micro, -1) + labels.shape[1:])
        params, opt_state, metrics = step_fn(params, opt_state, tokens,
                                             labels)
        if i % log_every == 0 or i == n_steps - 1:
            history.append({k: float(v) for k, v in metrics.items()})
            history[-1]["step"] = i
        if callback is not None:
            callback(i, params, opt_state, metrics)
    return params, opt_state, history
