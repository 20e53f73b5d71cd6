"""Cross-pod gradient compression: int8 all-gather with error feedback.

Within a pod, gradients synchronize over the fast ICI fabric (GSPMD inserts
the reduce inside backward). *Across* pods the link is DCN -- the slow, paid
link -- so the cross-pod exchange is made explicit and compressed:

  1. the batch is sharded over ('pod', 'data'); shard_map manual over 'pod'
     (auto over the rest) yields per-pod mean gradients;
  2. each tensor is quantized to int8 against a shared scale
     (pmax of per-pod absmax over 'pod');
  3. int8 payloads are all-gathered over 'pod' (1 byte/elem/pod on the wire
     vs 4 for an f32 ring all-reduce -> ~4x DCN traffic reduction, 2x vs
     bf16) and summed locally in int32;
  4. quantization error is fed back into the next step's gradient (error
     feedback keeps the scheme unbiased over time).

The error-feedback buffers live in the optimizer state pytree and shard like
the gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize(x, scale):
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return q.astype(jnp.int8)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(grads, errors, axis: str, n_pods: int):
    """Per-tensor int8 all-gather mean over ``axis`` with error feedback.

    Call inside shard_map (manual over ``axis``). Returns
    (mean_grads, new_errors).
    """

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        local_max = jnp.max(jnp.abs(g32))
        shared_max = jax.lax.pmax(local_max, axis)
        scale = jnp.maximum(shared_max, 1e-12) / 127.0
        q = quantize(g32, scale)
        new_e = g32 - dequantize(q, scale)            # error feedback
        gathered = jax.lax.all_gather(q, axis)        # int8 on the wire
        total = gathered.astype(jnp.int32).sum(axis=0)
        mean = dequantize(total, scale) / n_pods
        return mean.astype(g.dtype), new_e

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(errors)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return tdef.unflatten([o[0] for o in out]), tdef.unflatten([o[1] for o in out])


def init_error_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
