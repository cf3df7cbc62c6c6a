"""Layer mathematics of the windowed grouped-query layer kinds, as plain
functions over one layer's parameter tree: what a whole-sequence module
(``transformers/<model>/modeling.py``, no cache) and the serving step programs
(``experimental/window_model.py``, paged planes) both compute, from the same code.

Two kinds of layer, named as a configuration's ``layer_kinds()`` yields them:

- ``gqa_window``  grouped-query attention over a window (a query sees itself and
                  the ``window - 1`` positions before it), q and k rotated;
- ``gqa_full``    grouped-query attention over the whole context, **nothing
                  rotated**: the window layers carry position;
- ``gqa_block``   grouped-query attention over the whole context under a **block
                  mask** (generation by diffusion over blocks: causal over blocks
                  of ``block`` positions counted from position 0, a query seeing
                  its own block whole), q and k rotated.

In both, q and k pass an RMS norm over each head's dims (one learned scale of
``head_dim``, shared by the heads) before any rotation. Either kind's MLP is
``latent_layers.mlp``: dense SwiGLU or sigmoid-routed experts of which this
process holds a share.

What they read of a configuration ``cfg``: ``attention_dims()`` (heads,
kv_heads, head_dim, theta, window) and ``rms_norm_eps``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .latent_layers import NEG, rms_norm, rope

GQA_WINDOW, GQA_FULL, GQA_BLOCK = "gqa_window", "gqa_full", "gqa_block"
ROTATED = (GQA_WINDOW, GQA_BLOCK)


def project_qkv(p, x, positions, d, kind, eps):
    """x [B, T, hidden] at ``positions`` [B, T] -> (q [B, T, heads, head_dim], k
    and v [B, T, kv_heads, head_dim]): projections, the per-head RMS norm of q
    and k in float32 on the projections as they come (cast back to their
    dtype), then rotate-half RoPE over the whole head on window and block layers only."""
    b, t, _ = x.shape
    mm = lambda name, n: (x @ p[name]["kernel"].astype(x.dtype)).reshape(b, t, n, d["head_dim"])
    with jax.named_scope("qkv"):
        q, k, v = mm("q_proj", d["heads"]), mm("k_proj", d["kv_heads"]), mm("v_proj", d["kv_heads"])
    with jax.named_scope("qk_norm"):
        q, k = rms_norm(q, p["q_norm"]["scale"], eps), rms_norm(k, p["k_norm"]["scale"], eps)
    if kind in ROTATED:
        with jax.named_scope("rope"):
            q, k = rope(q, positions[..., None], d["theta"]), rope(k, positions[..., None], d["theta"])
    return q, k, v


def attend(q, k, v, allowed):
    """Softmax attention of q [B, T, N, H] over k, v [B, S, K, H] under
    ``allowed`` [B, T, S], query head n reading KV head n // (N / K); scores and
    softmax in float32. A row nothing is allowed for gives a uniform average,
    which no caller reads."""
    b, t, n, h = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, t, kv, n // kv, h)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k, preferred_element_type=jnp.float32) * h ** -0.5
    prob = jax.nn.softmax(jnp.where(allowed[:, None, None], s, NEG), axis=-1)
    return jnp.einsum("bkgts,bskh->btkgh", prob.astype(v.dtype), v).reshape(b, t, n, h)


def window_mask(q_pos, k_pos, window, block=None):
    """[..., T, S] bool: key position visible to query position, causal, and
    within ``window`` positions (the query itself counted) where it is not None.
    ``block`` (a power of two, None by default): causal over blocks of that many
    positions instead, a query seeing its own block whole."""
    if block is not None:
        q_pos = q_pos | (block - 1)
    seen = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        seen &= k_pos[..., None, :] > q_pos[..., :, None] - window
    return seen


def attention_dense(p, x, positions, cfg, kind):
    """One attention kind over whole sequences x [B, T, hidden]: the module's forward."""
    d = cfg.attention_dims()
    q, k, v = project_qkv(p, x, positions, d, kind, cfg.rms_norm_eps)
    o = attend(q, k, v, window_mask(positions, positions, d["window"] if kind == GQA_WINDOW else None,
                                    d["block"] if kind == GQA_BLOCK else None))
    return o.reshape(x.shape[:2] + (-1,)) @ p["o_proj"]["kernel"].astype(x.dtype)
