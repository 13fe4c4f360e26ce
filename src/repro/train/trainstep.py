"""train_step factory: chunked cross-entropy, microbatch gradient
accumulation, remat — the function ``build_cell`` lowers for ``train_*`` cells.

Memory notes (why chunked CE): full logits for train_4k on qwen3-moe would
be (16, 4096, 151936) per device — tens of GB. The loss contracts hidden
states against the unembedding one sequence-chunk at a time inside a scan,
so peak logits memory is (B, chunk, V/TP).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.sharding import shard
from repro.models.transformer import Model
from repro.train import optimizer as opt_lib


def chunked_cross_entropy(hidden, embed_params, labels, cfg,
                          chunk: int = 512) -> jax.Array:
    """hidden (B,S,d); labels (B,S) with -100 = ignore. Mean NLL."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    w = (embed_params["tok"].T if "unembed" not in embed_params
         else embed_params["unembed"])
    nc = S // chunk
    h = hidden.reshape(B, nc, chunk, d).swapaxes(0, 1)
    y = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    def body(carry, xs):
        loss_sum, count = carry
        h_c, y_c = xs
        logits = jnp.einsum("bsd,dv->bsv", h_c.astype(jnp.float32),
                            w.astype(jnp.float32))
        logits = shard(logits, "batch", "seq", "vocab")
        mask = y_c != -100
        safe_y = jnp.where(mask, y_c, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe_y[..., None],
                                   axis=-1)[..., 0]
        nll = jnp.where(mask, lse - gold, 0.0)
        return (loss_sum + jnp.sum(nll),
                count + jnp.sum(mask.astype(jnp.float32))), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (h, y))
    return loss_sum / jnp.maximum(count, 1.0)


def make_loss_fn(model: Model, ce_chunk: int = 512) -> Callable:
    def loss_fn(params, batch):
        hidden, aux = model.forward_hidden(params, batch, train=True)
        loss = chunked_cross_entropy(hidden, params["embed"],
                                     batch["labels"], model.cfg,
                                     chunk=ce_chunk)
        return loss + aux, {"ce_loss": loss, "aux_loss": aux}
    return loss_fn


def make_train_step(model: Model, opt_cfg: opt_lib.OptimizerConfig,
                    accum_steps: int = 1, ce_chunk: int = 512) -> Callable:
    """Returns train_step(params, opt_state, batch) ->
    (params, opt_state, metrics). Microbatches split the leading batch dim
    when accum_steps > 1 (grads accumulated in f32)."""
    loss_fn = make_loss_fn(model, ce_chunk)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, opt_state, batch):
        (loss, parts), grads = grad_fn(params, batch)
        params, opt_state, om = opt_lib.apply_updates(opt_cfg, params, grads,
                                                      opt_state)
        metrics = {"loss": loss, **parts, **om}
        return params, opt_state, metrics

    if accum_steps == 1:
        return single

    def accumulated(params, opt_state, batch):
        def split(x):
            B = x.shape[0]
            return x.reshape(accum_steps, B // accum_steps, *x.shape[1:])

        micro = jax.tree_util.tree_map(split, batch)
        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, mb):
            g_acc, l_acc = carry
            (loss, _), grads = grad_fn(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) / accum_steps,
                g_acc, grads)
            return (g_acc, l_acc + loss / accum_steps), None

        (grads, loss), _ = jax.lax.scan(body, (zero, jnp.float32(0.0)),
                                        micro)
        params, opt_state, om = opt_lib.apply_updates(opt_cfg, params, grads,
                                                      opt_state)
        return params, opt_state, {"loss": loss, **om}

    return accumulated


def make_eval_step(model: Model, ce_chunk: int = 512) -> Callable:
    loss_fn = make_loss_fn(model, ce_chunk)

    def eval_step(params, batch):
        loss, parts = loss_fn(params, batch)
        return {"loss": loss, **parts}

    return eval_step
