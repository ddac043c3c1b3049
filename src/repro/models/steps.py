"""Step functions: the units the launcher jits, shards, and dry-runs.

``train_step``  — forward + loss + backward + AdamW update (+ optional
                  microbatch gradient accumulation and int8 gradient
                  compression).
``prefill_step``— full-sequence forward building decode caches.
``decode_step`` — one token against the caches (see models/decode.py).

All are pure functions of (params, state, batch) suitable for
``jax.jit(..., in_shardings=..., out_shardings=...)``.  ``train_step`` names
its work for the device profiler (``jax.named_scope``): the root
``train_step``, ``loss`` around the forward and backward passes (the
backward's ops read ``transpose(jvp(loss))``), and ``optimizer`` around
clipping and the AdamW update; the model's own scopes nest inside ``loss``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import decode as dec
from repro.models.transformer import DistContext, forward
from repro.optim import adamw
from repro.optim.schedule import warmup_cosine


def next_token_loss(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    *,
    frontend: Optional[jax.Array] = None,
    dist: Optional[DistContext] = None,
    remat: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean next-token cross-entropy (+ MoE aux loss)."""
    logits, aux = forward(
        cfg, params, tokens, frontend=frontend, dist=dist, remat=remat
    )
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    labels = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = nll.mean()
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


@jax.named_scope("train_step")
def train_step(
    cfg: ModelConfig,
    run: RunConfig,
    params: dict,
    opt_state: adamw.AdamWState,
    batch: Dict[str, jax.Array],  # {"tokens": (B,S)[, "frontend": ...]}
    *,
    dist: Optional[DistContext] = None,
) -> Tuple[dict, adamw.AdamWState, Dict[str, jax.Array]]:
    """One optimizer step.  ``run.n_microbatches > 1`` accumulates gradients
    over microbatches inside a scan (activation memory O(microbatch); the
    per-microbatch reduce structure lets the scheduler overlap grad
    collectives of microbatch i with the backward of i+1)."""
    tokens = batch["tokens"]
    frontend = batch.get("frontend")

    remat_mode = run.remat_policy if run.remat else "none"

    # under value_and_grad the backward pass carries the scope too, as
    # transpose(jvp(loss))
    @jax.named_scope("loss")
    def loss_fn(p, toks, fr):
        return next_token_loss(
            cfg, p, toks, frontend=fr, dist=dist, remat=remat_mode
        )

    n_micro = max(run.n_microbatches, 1)
    B = tokens.shape[0]
    if n_micro > 1 and B % n_micro == 0:
        mtoks = tokens.reshape((n_micro, B // n_micro) + tokens.shape[1:])
        mfr = (
            frontend.reshape((n_micro, B // n_micro) + frontend.shape[1:])
            if frontend is not None
            else None
        )

        acc_dt = jnp.bfloat16 if run.grad_accum_dtype == "bfloat16" else jnp.float32

        def micro(acc, mb):
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb[0], mb[1] if mfr is not None else None
            )
            acc_l, acc_g = acc
            g = jax.tree.map(lambda x: x.astype(acc_dt), g)
            return (acc_l + l, jax.tree.map(jnp.add, acc_g, g)), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
        xs = (mtoks, mfr) if mfr is not None else (mtoks, mtoks)  # dummy 2nd
        (tot_l, grads), _ = jax.lax.scan(micro, (0.0, zero), xs)
        loss = tot_l / n_micro
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / n_micro, grads)
        metrics = {"loss": loss}
    else:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, frontend
        )

    with jax.named_scope("optimizer"):
        grads, gnorm = adamw.clip_by_global_norm(grads, run.grad_clip)
        lr = warmup_cosine(
            opt_state.step,
            peak_lr=run.learning_rate,
            warmup_steps=run.warmup_steps,
            total_steps=run.total_steps,
        )
        new_params, new_state = adamw.apply_updates(
            adamw.AdamWConfig(
                lr=run.learning_rate,
                weight_decay=run.weight_decay,
                grad_clip=run.grad_clip,
            ),
            params,
            grads,
            opt_state,
            lr=lr,
        )
    metrics = dict(metrics, grad_norm=gnorm, lr=lr)
    return new_params, new_state, metrics


def prefill_step(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    *,
    frontend: Optional[jax.Array] = None,
    capacity: Optional[int] = None,
    dist: Optional[DistContext] = None,
):
    return dec.prefill(
        cfg, params, tokens, frontend=frontend, capacity=capacity, dist=dist
    )


def decode_step(
    cfg: ModelConfig,
    params: dict,
    caches: tuple,
    token: jax.Array,
    pos: jax.Array,
    *,
    dist: Optional[DistContext] = None,
):
    return dec.decode_step(cfg, params, caches, token, pos, dist=dist)
