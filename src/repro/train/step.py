"""Train/serve step factories: pjit-compiled, sharded, microbatched.

``make_train_step`` builds the jitted update used by the training loop, the
launcher and the dry-run. The same factory serves the 40-cell dry-run (it is
lowered with ShapeDtypeStructs) and real training (smoke scale on CPU).

Gradient accumulation: the global batch is reshaped to
(microbatches, B/microbatches, ...) and scanned; grads are averaged in f32.
With FSDP-sharded params this is ZeRO-style: grads inherit the parameter
sharding (reduce-scattered by GSPMD), optimizer state is sharded likewise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.dist import sharding as shd
from repro.models.model import LM
from repro.train.optimizer import OptState, make_optimizer

__all__ = [
    "TrainState",
    "make_train_state",
    "init_train_state",
    "make_train_step",
    "make_serve_steps",
]


TrainState = dict  # {"params": pytree, "opt": OptState}


def make_train_state(lm: LM, tcfg: TrainConfig, key) -> TrainState:
    params = lm.init(key)
    opt_init, _ = make_optimizer(tcfg)
    return {"params": params, "opt": opt_init(params)}


def init_train_state(
    lm: LM, tcfg: TrainConfig, pcfg: ParallelConfig, mesh: Mesh, key
) -> TrainState:
    """Build the train state under jit, straight onto its target shardings:
    each device materialises only its own shard, and no f32 draw of a
    stacked parameter leaf ever exists whole (at 7B widths one such leaf
    alone is 5 GiB)."""
    init = lambda k: make_train_state(lm, tcfg, k)
    shardings = state_shardings(jax.eval_shape(init, key), pcfg, mesh)
    return jax.jit(init, out_shardings=shardings)(key)


def state_shardings(state, pcfg: ParallelConfig, mesh: Mesh):
    """Opt state mirrors param sharding (ZeRO); factored stats tighten."""
    pspecs = shd.param_specs(state["params"], pcfg, mesh)

    def opt_leaf(path, x):
        # OptState.m / .v mirror params structure below the NamedTuple field
        return None

    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    def mirror(tree):
        """Shard each moment leaf like its param (tighten for factored)."""

        def leaf(path, x):
            spec = shd.spec_for(shd._path_str(path), x.shape, pcfg, mesh)
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(leaf, tree)

    opt = state["opt"]
    return {
        "params": pshard,
        "opt": OptState(
            step=NamedSharding(mesh, P()),
            m=mirror(opt.m),
            v=mirror(opt.v),
        ),
    }


def make_train_step(
    lm: LM,
    tcfg: TrainConfig,
    pcfg: ParallelConfig,
    mesh: Mesh,
):
    """Returns (jitted_step, in_shardings info) — step(state, batch) ->
    (state, metrics)."""
    _, opt_update = make_optimizer(tcfg)
    n_micro = max(1, pcfg.microbatches)

    def loss_fn(params, batch):
        loss, metrics = lm.loss(params, batch)
        return loss, metrics

    def step(state, batch):
        params = state["params"]

        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:

            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params
            )
            mbatch = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                batch,
            )
            (grads, loss_sum), metrics = jax.lax.scan(micro, (g0, 0.0), mbatch)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
            metrics = jax.tree.map(lambda x: x.mean(0), metrics)

        new_params, new_opt, stats = opt_update(grads, state["opt"], params)
        metrics = dict(metrics, **stats, loss_mean=loss)
        return {"params": new_params, "opt": new_opt}, metrics

    def shardings_for(state, batch):
        st_sh = state_shardings(state, pcfg, mesh)
        b_sh = shd.batch_shardings(batch, pcfg, mesh)
        return st_sh, b_sh

    def compile_step(state_spec, batch_spec):
        st_sh, b_sh = shardings_for(state_spec, batch_spec)
        return jax.jit(
            step,
            in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, None),
            donate_argnums=(0,),
        )

    return step, compile_step


def make_serve_steps(lm: LM, pcfg: ParallelConfig, mesh: Mesh, *, max_len: int):
    """prefill(params, batch) -> (logits, caches); decode(params, tok, caches)."""

    def prefill(params, batch):
        return lm.prefill(params, batch, max_len)

    def decode(params, tokens, caches):
        return lm.decode_step(params, tokens, caches)

    def compile_prefill(params_spec, batch_spec):
        p_sh = shd.param_shardings(params_spec, pcfg, mesh)
        b_sh = shd.batch_shardings(batch_spec, pcfg, mesh)
        return jax.jit(prefill, in_shardings=(p_sh, b_sh))

    def compile_decode(params_spec, tok_spec, caches_spec):
        p_sh = shd.param_shardings(params_spec, pcfg, mesh)
        t_sh = shd.batch_shardings(tok_spec, pcfg, mesh)
        c_sh = shd.cache_shardings(caches_spec, pcfg, mesh)
        return jax.jit(
            decode, in_shardings=(p_sh, t_sh, c_sh), donate_argnums=(2,)
        )

    return prefill, decode, compile_prefill, compile_decode
