"""Jitted train/serve step builders with explicit in/out shardings.

``make_train_step`` wires model.train_loss -> grads -> AdamW into one jitted,
donated step. With ``compress_pods=True`` on a multi-pod mesh, the step is
wrapped in a shard_map manual over 'pod' (auto over 'data'/'model'): each pod
computes its own gradient under GSPMD, and the cross-pod exchange goes
through train/compression.py (int8 all-gather + error feedback) instead of
the implicit f32 all-reduce -- the DCN link is the slow one at multi-pod
scale (DESIGN.md S6).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.train import compression as comp
from repro.train.optimizer import AdamWConfig, adamw_update


def make_train_step(model, opt_cfg: AdamWConfig, *, compress_pods: bool = False,
                    param_specs=None):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    The caller jits this with in/out shardings (launch/train.py, dryrun.py).

    ``param_specs`` pins the GRADIENT sharding to the parameter sharding.
    Without it, GSPMD is free to materialize replicated f32 gradients inside
    the layer scan and all-reduce them (measured on grok/arctic: ~20 GB
    all-reduces per layer, EXPERIMENTS.md SPerf); the constraint makes the
    backward emit reduce-scatters into the FSDP shards instead.
    """
    mesh = model.mesh
    has_pod = mesh is not None and "pod" in mesh.axis_names

    def loss_fn(p, batch):
        loss, aux = model.train_loss(p, batch)
        return loss, aux

    def constrain(grads):
        if param_specs is None or mesh is None:
            return grads
        from jax.sharding import PartitionSpec as P

        def one(sp, g):
            try:
                return jax.lax.with_sharding_constraint(g, sp)
            except (ValueError, RuntimeError):
                return g

        return jax.tree.map(one, param_specs, grads,
                            is_leaf=lambda x: isinstance(x, P))

    if not (compress_pods and has_pod):
        def step(params, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            grads = constrain(grads)
            params, opt_state, om = adamw_update(grads, opt_state, params,
                                                 opt_cfg)
            metrics = {"loss": loss, **aux, **om}
            return params, opt_state, metrics
        return step

    n_pods = mesh.shape["pod"]

    def pod_partials(params, batch, errors):
        """Per-pod grads + exchange via shard_map manual over 'pod' only
        ('data'/'model' remain GSPMD-auto inside)."""

        def per_pod(params, batch, errors):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            grads, new_errors = comp.compressed_psum_mean(
                grads, errors, "pod", n_pods)
            loss = jax.lax.pmean(loss, "pod")
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, "pod"), aux)
            return loss, aux, grads, new_errors

        pspecs = jax.tree.map(lambda _: P(), params)
        espisos = jax.tree.map(lambda _: P(), errors)
        batch_specs = jax.tree.map(lambda _: P("pod"), batch)
        from repro.compat import shard_map

        return shard_map(
            per_pod, mesh=mesh,
            in_specs=(pspecs, batch_specs, espisos),
            out_specs=(P(), jax.tree.map(lambda _: P(), aux_struct(model)),
                       pspecs, espisos),
            check_vma=False,
            axis_names={"pod"},
        )(params, batch, errors)

    def step(params, opt_state, batch):
        errors = opt_state["grad_error"]
        loss, aux, grads, new_errors = pod_partials(params, batch, errors)
        opt_state = dict(opt_state)
        opt_state["grad_error"] = new_errors
        inner = {k: opt_state[k] for k in ("step", "master", "m", "v")}
        params, inner, om = adamw_update(grads, inner, params, opt_cfg)
        opt_state.update(inner)
        metrics = {"loss": loss, **aux, **om}
        return params, opt_state, metrics

    return step


def aux_struct(model):
    return {"dropped_frac": 0.0}


def make_eval_step(model):
    def step(params, batch):
        loss, aux = model.train_loss(params, batch)
        return {"loss": loss, **aux}
    return step


def make_decode_step(model):
    def step(params, tokens, caches):
        return model.decode_step(params, tokens, caches)
    return step


def make_prefill_step(model):
    def step(params, batch, caches):
        return model.prefill(params, batch, caches)
    return step
