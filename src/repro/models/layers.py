"""Shared layers: parameters carry sharding specs as a parallel pytree.

Every parameter-creating helper returns ``(array, spec)`` where spec is a
``jax.sharding.PartitionSpec``; model init assembles parallel (params, specs)
trees. The convention for 2-D weights is P(fsdp, tp): the input dimension is
sharded over the FSDP ('data') axis, the output over the tensor ('model')
axis, unless a dimension is not divisible -- then that dim is replicated
(recorded by the config's layout report, see launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _divisible(dim: int, axis_size: int) -> bool:
    return axis_size > 0 and dim % axis_size == 0


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh axis names/sizes the init code uses to pick legal specs."""

    fsdp_axis: Optional[str]   # usually 'data' (+'pod' folded by the mesh)
    tp_axis: Optional[str]     # usually 'model'
    fsdp_size: int
    tp_size: int

    def axis(self, kind: str, dim: int):
        """Return the axis name for ``kind`` if ``dim`` divides, else None."""
        if kind == "tp" and self.tp_axis and _divisible(dim, self.tp_size):
            return self.tp_axis
        if kind == "fsdp" and self.fsdp_axis and _divisible(dim, self.fsdp_size):
            return self.fsdp_axis
        return None


def dense_param(key, d_in: int, d_out: int, ctx: ShardCtx, dtype,
                *, tp_dim: str = "out", scale: Optional[float] = None):
    """Weight (d_in, d_out); TP on ``tp_dim``, FSDP on the other dim."""
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype) * jnp.asarray(scale, dtype)
    if tp_dim == "out":
        spec = P(ctx.axis("fsdp", d_in), ctx.axis("tp", d_out))
    else:
        spec = P(ctx.axis("tp", d_in), ctx.axis("fsdp", d_out))
    return w, spec


def bias_param(d: int, ctx: ShardCtx, dtype, *, tp: bool):
    b = jnp.zeros((d,), dtype)
    return b, P(ctx.axis("tp", d) if tp else None)


def embed_param(key, vocab: int, d_model: int, ctx: ShardCtx, dtype):
    w = jax.random.normal(key, (vocab, d_model), dtype) * jnp.asarray(0.02, dtype)
    return w, P(ctx.axis("tp", vocab), None)


def norm_param(d: int, dtype):
    return jnp.ones((d,), dtype), P(None)


def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def rope_frequencies(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits, labels, *, z_loss: float = 0.0):
    """Mean token CE in f32; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * lse**2
    mask = labels >= 0
    return jnp.sum(loss * mask) / jnp.maximum(mask.sum(), 1)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def shard(x, *spec):
    """with_sharding_constraint that tolerates running outside a mesh."""
    from repro.compat import sharding_constraint

    try:
        return sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x
