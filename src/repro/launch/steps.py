"""Step-function builders for the launchers.

For a (config, shape-suite, mesh) cell this produces the jit-wrapped
function with full in/out shardings plus abstract (ShapeDtypeStruct)
arguments — everything ``.lower().compile()`` needs, with zero allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSuite
from repro.launch import sharding as shp
from repro.models import build_model, extra_inputs, input_specs
from repro.train.optimizer import OptimizerConfig, init_state
from repro.train.trainstep import make_train_step


def _named(mesh, tree):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), tree,
                                  is_leaf=lambda x: isinstance(x, P))


def abstract_params(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def build_train_cell(cfg: ModelConfig, suite: ShapeSuite, mesh, rules,
                     accum_steps: int = 1, ce_chunk: int = 512,
                     remat: str = "block"):
    """Returns (jitted_fn, abstract_args) for train_step."""
    if cfg.remat == "none" and remat != "none":
        cfg = dataclasses.replace(cfg, remat=remat)
    model = build_model(cfg)
    opt_cfg = OptimizerConfig()
    step = make_train_step(model, opt_cfg, accum_steps=accum_steps,
                           ce_chunk=min(ce_chunk, suite.seq_len))

    p_abs = abstract_params(model)
    opt_abs = jax.eval_shape(init_state, p_abs)
    batch_abs = input_specs(cfg, suite)

    p_spec = shp.param_specs(p_abs, cfg, mesh, rules)
    opt_spec = {"step": P(), "mu": p_spec, "nu": p_spec}
    b_spec = shp.batch_specs(batch_abs, rules)

    metrics_sharding = None  # replicated scalars
    jitted = jax.jit(
        step,
        in_shardings=(_named(mesh, p_spec), _named(mesh, opt_spec),
                      _named(mesh, b_spec)),
        out_shardings=(_named(mesh, p_spec), _named(mesh, opt_spec),
                       metrics_sharding),
        donate_argnums=(0, 1),
    )
    return jitted, (p_abs, opt_abs, batch_abs)


def build_prefill_cell(cfg: ModelConfig, suite: ShapeSuite, mesh, rules):
    """prefill(params, tokens, lengths, cache, extra) -> (logits, cache)."""
    model = build_model(cfg)
    p_abs = abstract_params(model)
    cache_dtype = jnp.dtype(cfg.kv_cache_dtype)
    cache_abs = jax.eval_shape(
        lambda: model.init_cache(suite.global_batch, suite.seq_len,
                                 cache_dtype))
    specs = input_specs(cfg, suite)
    tokens_abs = specs["tokens"]
    lengths_abs = specs["lengths"]
    extra_abs = extra_inputs(cfg, suite.global_batch) or None

    p_spec = shp.param_specs(p_abs, cfg, mesh, rules)
    c_spec = shp.cache_specs(cache_abs, cfg, mesh, rules,
                             suite.global_batch, suite.seq_len)
    b = rules.get("batch")
    extra_spec = (jax.tree_util.tree_map(
        lambda s: P(*((b,) + (None,) * (len(s.shape) - 1))), extra_abs)
        if extra_abs else None)

    def fn(params, tokens, lengths, cache, extra):
        return model.prefill(params, tokens, lengths, cache, extra=extra)

    jitted = jax.jit(
        fn,
        in_shardings=(_named(mesh, p_spec), NamedSharding(mesh, P(b, None)),
                      NamedSharding(mesh, P(b)), _named(mesh, c_spec),
                      _named(mesh, extra_spec) if extra_spec else None),
        out_shardings=(NamedSharding(mesh, P(b, None)),
                       _named(mesh, c_spec)),
        donate_argnums=(3,),
    )
    return jitted, (p_abs, tokens_abs, lengths_abs, cache_abs, extra_abs)


def build_decode_cell(cfg: ModelConfig, suite: ShapeSuite, mesh, rules):
    """serve_step: one new token against a seq_len cache."""
    model = build_model(cfg)
    p_abs = abstract_params(model)
    cache_dtype = jnp.dtype(cfg.kv_cache_dtype)
    cache_abs = jax.eval_shape(
        lambda: model.init_cache(suite.global_batch, suite.seq_len,
                                 cache_dtype))
    specs = input_specs(cfg, suite)

    p_spec = shp.param_specs(p_abs, cfg, mesh, rules)
    c_spec = shp.cache_specs(cache_abs, cfg, mesh, rules,
                             suite.global_batch, suite.seq_len)
    b = rules.get("batch")

    def serve_step(params, tokens, lengths, cache):
        return model.decode_step(params, tokens, lengths, cache)

    jitted = jax.jit(
        serve_step,
        in_shardings=(_named(mesh, p_spec), NamedSharding(mesh, P(b, None)),
                      NamedSharding(mesh, P(b)), _named(mesh, c_spec)),
        out_shardings=(NamedSharding(mesh, P(b, None)),
                       _named(mesh, c_spec)),
        donate_argnums=(3,),
    )
    return jitted, (p_abs, specs["tokens"], specs["lengths"], cache_abs)


def build_cell(cfg: ModelConfig, suite: ShapeSuite, mesh,
               rules: Optional[Dict] = None, **kw):
    rules = rules if rules is not None else shp.make_rules(cfg, mesh, suite)
    if suite.kind == "train":
        fn, args = build_train_cell(cfg, suite, mesh, rules, **kw)
    elif suite.kind == "prefill":
        fn, args = build_prefill_cell(cfg, suite, mesh, rules)
    else:
        fn, args = build_decode_cell(cfg, suite, mesh, rules)
    return fn, args, rules

