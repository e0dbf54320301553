"""Train-step builder: loss -> grads -> clip -> optimizer, with optional
gradient accumulation (microbatch scan — XLA overlaps microbatch i's DP
all-reduce with microbatch i+1's compute) and the power-capping phase ledger.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import lm
from repro.models.layers import Ctx
from repro.optim import Adafactor, AdamW, clip_by_global_norm, warmup_cosine
from repro.train.loss import chunked_cross_entropy


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    def tree(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "step": self.step}

    @classmethod
    def from_tree(cls, t):
        return cls(params=t["params"], opt_state=t["opt_state"],
                   step=t["step"])


def make_optimizer(run: RunConfig):
    lr = warmup_cosine(run.learning_rate, run.warmup_steps, run.total_steps)
    if run.optimizer == "adafactor":
        # factored second moments: ~4 bytes/param of optimizer state instead
        # of AdamW's 8 — the memory-term lever for the largest archs
        return Adafactor(lr=lr, weight_decay=run.weight_decay)
    return AdamW(lr=lr, b1=run.beta1, b2=run.beta2,
                 weight_decay=run.weight_decay)


def init_state(cfg: ModelConfig, run: RunConfig, key) -> TrainState:
    from repro.models.params import init_params
    decls = lm.model_decls(cfg)
    params = init_params(decls, key, run.param_dtype)
    opt = make_optimizer(run)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def abstract_state(cfg: ModelConfig, run: RunConfig) -> dict:
    """ShapeDtypeStruct version of the state tree (dry-run)."""
    from repro.models.params import abstract_params
    decls = lm.model_decls(cfg)
    params = abstract_params(decls)
    opt = make_optimizer(run)
    opt_state = jax.eval_shape(opt.init, params)
    return {"params": params,
            "opt_state": opt_state,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def state_logical_axes(cfg: ModelConfig, run: RunConfig | None = None) -> dict:
    from repro.models.params import logical_axes
    axes = logical_axes(lm.model_decls(cfg))
    if run is not None and run.optimizer == "adafactor":
        def f_axes(a):
            if len(a) >= 2:
                return {"vr": tuple(a[:-1]),
                        "vc": tuple(a[:-2]) + (a[-1],)}
            return {"v": tuple(a)}
        opt_axes = {"f": jax.tree.map(
            f_axes, axes, is_leaf=lambda x: isinstance(x, tuple))}
    else:
        opt_axes = {"m": axes, "v": axes}
    return {"params": axes,
            "opt_state": opt_axes,
            "step": ()}


def make_loss_fn(cfg: ModelConfig, run: RunConfig, ctx: Ctx):
    # no kernel in repro.kernels defines a backward (no custom_vjp), so the
    # differentiated forward runs the XLA attention and SSD path
    ctx = dataclasses.replace(ctx, run=ctx.run.replace(kernel_mode="reference"))

    def loss_fn(params, batch):
        h, aux, _ = lm.forward(ctx, cfg, params, batch)
        labels = batch["labels"]
        loss, metrics = chunked_cross_entropy(ctx, cfg, params, h, labels)
        if cfg.n_experts:
            loss = loss + cfg.router_aux_weight * aux
            metrics = dict(metrics, aux=aux)
        return loss, metrics
    return loss_fn


def make_train_step(cfg: ModelConfig, run: RunConfig, ctx: Ctx):
    """Returns train_step(state_tree, batch) -> (state_tree, metrics)."""
    opt = make_optimizer(run)
    loss_fn = make_loss_fn(cfg, run, ctx)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if run.grad_accum <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        # microbatch accumulation: reshape leading batch dim and scan
        def split(x):
            b = x.shape[0]
            return x.reshape((run.grad_accum, b // run.grad_accum)
                             + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            acc, loss_acc = carry
            (loss, _metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (gsum, loss_sum), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), micro)
        inv = 1.0 / run.grad_accum
        grads = jax.tree.map(lambda g: g * inv, gsum)
        loss = loss_sum * inv
        return loss, {"ce": loss}, grads

    def train_step(state, batch):
        params, opt_state, step = (state["params"], state["opt_state"],
                                   state["step"])
        loss, metrics, grads = compute_grads(params, batch)
        if run.grad_compression == "int8":
            from repro.train.compression import int8_compress_decompress
            grads = int8_compress_decompress(grads)
        grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
        new_params, new_opt = opt.update(grads, opt_state, params, step)
        out = {"params": new_params, "opt_state": new_opt, "step": step + 1}
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return out, metrics

    return train_step
