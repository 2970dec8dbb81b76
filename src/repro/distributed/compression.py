"""Cross-pod gradient compression: int8 quantization with error feedback.

Inter-pod links (data-center network between slices) are far slower than
in-pod ICI, so the pod-axis all-reduce is the one worth compressing. Scheme:

    g_fb   = g + err                        # error feedback (memory = g shape)
    scale  = pmax(|g_fb|) / 127             # shared scale across the axis
    q      = round(g_fb / scale)  in int8 range
    g_out  = psum(q) * scale / N            # mean gradient
    err'   = g_fb - q * scale               # local residual, fed back next step

Error feedback makes the quantization bias telescope away (Karimireddy et
al. 2019); tests check exact-mean recovery for constant gradients and
bounded error + convergence of the residual otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def compressed_pmean(g, err, axis_name: str):
    """int8 error-feedback psum-mean along `axis_name` (inside shard_map).

    Returns (g_mean, new_err). Works leaf-wise on pytrees.
    """

    def one(g, err):
        g_fb = g + err
        amax = jax.lax.pmax(jnp.max(jnp.abs(g_fb)), axis_name)
        scale = jnp.maximum(amax / 127.0, 1e-30)
        q = jnp.clip(jnp.round(g_fb / scale), -127, 127)
        n = jax.lax.axis_size(axis_name)
        g_mean = jax.lax.psum(q, axis_name) * scale / n
        new_err = g_fb - q * scale
        return g_mean.astype(g.dtype), new_err.astype(err.dtype)

    flat_g, tree = jax.tree_util.tree_flatten(g)
    flat_e = jax.tree_util.tree_leaves(err)
    out = [one(a, b) for a, b in zip(flat_g, flat_e)]
    g_out = jax.tree_util.tree_unflatten(tree, [o[0] for o in out])
    e_out = jax.tree_util.tree_unflatten(tree, [o[1] for o in out])
    return g_out, e_out


def quantize_slab(x, err=None):
    """Sender-side int8 quantization of one halo slab (+ error feedback).

    Unlike `compressed_pmean` the scale is LOCAL (max over this slab only,
    no collective): a halo exchange ships point-to-point, so the receiver
    just needs the sender's scale shipped alongside the int8 payload — one
    extra f32 word per slab vs a whole collective for a shared scale.

    Returns (q_int8, scale_f32_scalar, new_err_f32). `err` is the residual
    from the PREVIOUS quantization of the same slab (error feedback, f32 so
    sub-32-bit streams don't lose the telescoping); None means no feedback.
    """
    x_fb = x.astype(jnp.float32) if err is None else x.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(x_fb)) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(x_fb / scale), -127, 127).astype(jnp.int8)
    new_err = x_fb - q.astype(jnp.float32) * scale
    return q, scale, new_err


def dequantize_slab(q, scale, dtype):
    """Reconstruct a halo slab from int8 payload + shipped scale."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_error_state(params):
    """Zero error-feedback residuals matching the `params` pytree."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def compression_ratio(dtype=jnp.float32) -> float:
    """Wire-bytes ratio vs uncompressed psum of `dtype` (int8 payload)."""
    return jnp.dtype(dtype).itemsize / 1.0
