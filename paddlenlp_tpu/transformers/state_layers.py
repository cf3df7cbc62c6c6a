"""Layer mathematics of the state-space layer kind, as plain functions over one
layer's parameter tree: what a whole-sequence module (``transformers/<model>/
modeling.py``, no cache) and the serving step programs
(``experimental/state_model.py``, state rows addressed by slot) both compute,
from the same code.

The kind, named as a configuration's ``layer_kinds()`` yields it:

- ``ssm``  a Mamba-2 mixer. ``[z | xBC | dt] = u W_in``; a causal depthwise
           convolution over ``xBC`` and SiLU; ``x`` [H, P], ``B`` and ``C`` [G, N]
           (head ``h`` reads group ``h // (H / G)``); ``dt = softplus(dt +
           dt_bias)``, ``A = -exp(A_log)`` a head;
           ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, a [P, N] state a
           head, float32; ``y_t = h_t C_t + D x_t``; ``y`` gated by ``SiLU(z)``,
           RMS-normed in groups, through ``W_out``.

A sequence's whole past is two arrays: the recurrent state [H, P, N] and the
last ``conv_kernel - 1`` inputs of the convolution. The recurrence has two
forms, picked by how many tokens a row feeds:

- ``ssd_chunk``  a chunk of tokens from a given state, in sub-chunks of
                 ``chunk_size`` (the SSD form: inside a sub-chunk a masked
                 matrix product, between sub-chunks the state). A position
                 with ``dt = 0`` leaves the state as it was: padding.
- ``ssm_scan``   token by token over a whole sequence (``lax.scan`` of
                 ``ssm_step``): what the other two are held against in tests.
- ``ssm_step``   one token: the recurrence as written.

What they read of a configuration: ``ssm_dims()`` (heads, head_dim, d_in,
groups, state, conv, conv_dim, chunk) and ``norm_eps``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .latent_layers import rms_norm  # noqa: F401  (the block norm, for the module and the step programs)

SSM, EXPERTS, ATTENTION = "ssm", "experts", "attention"
NEG = -1e30


def in_project(p, u, d):
    """u [B, T, hidden] -> (z [B, T, d_in], xbc [B, T, conv_dim] before the convolution, dt [B, T, H] raw).
    The in-projection ``[z | xBC | dt]`` is kept as its three column blocks: each is as wide as a whole number of
    128-lane tiles (or tiny), where the whole (10304 at the published sizes) is not, and a weight whose minor
    axis is padded is laid out anew by the chip's compiler, once a program and beside the original."""
    mm = lambda name: u @ p["in_proj"][name]["kernel"].astype(u.dtype)
    return mm("z"), mm("xbc"), mm("dt")


def causal_conv(p, xbc, before, d):
    """Depthwise causal convolution with bias, then SiLU. ``xbc`` [B, T, C] at
    consecutive positions, ``before`` [B, K - 1, C] the inputs of the K - 1
    positions ahead of them (zeros at a sequence's start). Returns (out
    [B, T, C], window [B, K - 1 + T, C]: every input in order, from which the
    caller cuts what the next call needs)."""
    k, t = d["conv"], xbc.shape[1]
    window = jnp.concatenate([before.astype(xbc.dtype), xbc], axis=1)
    w = p["conv1d"]["kernel"].astype(jnp.float32)  # [K, C]: tap j weighs the input K - 1 - j positions back
    acc = p["conv1d"]["bias"].astype(jnp.float32)
    for j in range(k):
        acc = acc + window[:, j: j + t].astype(jnp.float32) * w[j]
    return jax.nn.silu(acc).astype(xbc.dtype), window


def split_xbc(xbc, d):
    """xbc [B, T, conv_dim] after the convolution -> (x [B, T, G, R, P], B [B, T, G, N], C [B, T, G, N]),
    heads as (group, head in group)."""
    b, t, _ = xbc.shape
    g, n = d["groups"], d["state"]
    x = xbc[..., : d["d_in"]].reshape(b, t, g, d["heads"] // g, d["head_dim"])
    bmat = xbc[..., d["d_in"]: d["d_in"] + g * n].reshape(b, t, g, n)
    cmat = xbc[..., d["d_in"] + g * n:].reshape(b, t, g, n)
    return x, bmat, cmat


def step_sizes(p, dt_raw, valid, d):
    """dt [B, T, G, R] float32 = softplus(dt + dt_bias), 0 where not ``valid`` [B, T]; A [G, R] = -exp(A_log)."""
    g = d["groups"]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    dt = jnp.where(valid[..., None], dt, 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    return dt.reshape(dt.shape[:2] + (g, -1)), a.reshape(g, -1)


def ssd_chunk(x, dt, a, bmat, cmat, h0, chunk):
    """The recurrence over T tokens a row, from state ``h0`` [B, G, R, P, N]
    float32, in sub-chunks of ``chunk`` (the tail padded with dt = 0). x
    [B, T, G, R, P], dt [B, T, G, R] float32 (0 at padded positions), a [G, R],
    bmat / cmat [B, T, G, N]. Returns (y [B, T, G, R, P] float32 without the D
    term, the state after the last token with dt > 0)."""
    b, t = x.shape[:2]
    q = chunk
    pad = -t % q
    if pad:
        x, dt, bmat, cmat = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, bmat, cmat))
    cut = lambda v: jnp.moveaxis(v.reshape((b, (t + pad) // q, q) + v.shape[2:]), 1, 0)
    tri = jnp.tril(jnp.ones((q, q), bool))

    def sub(h, xs):
        xc, dtc, bc, cc = xs  # [B, Q, ...]
        dth = jnp.moveaxis(dtc, 1, -1)  # [B, G, R, Q]: positions minor, as the masked product wants them
        cum = jnp.cumsum(dth * a[..., None], axis=-1)  # falling from 0
        # decay from position j to position i >= j, and nothing where i < j
        decay = jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :], NEG))  # [B, G, R, i, j]
        cb = jnp.einsum("bign,bjgn->bgij", cc, bc, preferred_element_type=jnp.float32)
        m = cb[:, :, None] * decay * dth[..., None, :]
        y = jnp.einsum("bgrij,bjgrp->bigrp", m, xc, preferred_element_type=jnp.float32)
        # what the state before the sub-chunk still gives position i; highest: the state is float32
        carried = jnp.einsum("bign,bgrpn->bigrp", cc.astype(jnp.float32), h, precision="highest")
        y = y + jnp.moveaxis(jnp.exp(cum), -1, 1)[..., None] * carried
        to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum) * dth, -1, 1)  # [B, Q, G, R]
        grown = jnp.einsum("bjgrp,bjgn->bgrpn", to_end[..., None] * xc.astype(jnp.float32), bc,
                           preferred_element_type=jnp.float32)
        return jnp.exp(cum[..., -1])[..., None, None] * h + grown, y

    h, ys = jax.lax.scan(sub, h0, (cut(x), cut(dt), cut(bmat), cut(cmat)))
    return jnp.moveaxis(ys, 0, 1).reshape((b, t + pad) + ys.shape[3:])[:, :t], h


def ssm_step(x, dt, a, bmat, cmat, h0):
    """One token a row: x [B, 1, G, R, P], dt [B, 1, G, R], bmat / cmat [B, 1, G, N], h0 [B, G, R, P, N]
    float32 -> (y [B, 1, G, R, P] float32 without the D term, the new state)."""
    x32, b32, c32 = x[:, 0].astype(jnp.float32), bmat[:, 0].astype(jnp.float32), cmat[:, 0].astype(jnp.float32)
    dt0 = dt[:, 0]
    h = (jnp.exp(dt0 * a)[..., None, None] * h0
         + (dt0[..., None] * x32)[..., None] * b32[:, :, None, None, :])
    y = jnp.sum(h * c32[:, :, None, None, :], axis=-1)
    return y[:, None], h


def ssm_scan(x, dt, a, bmat, cmat, h0):
    """The recurrence as written, one ``ssm_step`` a position: the same arguments and results as ``ssd_chunk``."""
    per_token = lambda v: jnp.moveaxis(v, 1, 0)[:, :, None]

    def one(h, xs):
        y, h = ssm_step(*xs[:2], a, *xs[2:], h)
        return h, y[:, 0]

    h, ys = jax.lax.scan(one, h0, tuple(per_token(v) for v in (x, dt, bmat, cmat)))
    return jnp.moveaxis(ys, 0, 1), h


def gate_norm(p, y, z, d, eps):
    """RMSNorm in ``groups`` groups of ``y * SiLU(z)`` (the gate before the norm), learned scale. y, z [B, T, d_in]."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(gated.shape[:-1] + (d["groups"], -1))
    normed = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + eps)
    return (normed.reshape(gated.shape) * p["norm"]["scale"].astype(jnp.float32)).astype(z.dtype)


def ssm_mixer(p, u, valid, before, h0, d, eps):
    """The whole mixer on u [B, T, hidden] (normed input): ``valid`` [B, T]
    marks real positions, ``before`` [B, K - 1, C] and ``h0`` [B, G, R, P, N] are
    the row's past. Returns (out [B, T, hidden], conv window [B, K - 1 + T, C],
    new state). One token a row runs ``ssm_step``, more ``ssd_chunk``."""
    scope = jax.named_scope
    b, t, _ = u.shape
    with scope("ssm_proj"):
        z, xbc, dt_raw = in_project(p, u, d)
    with scope("ssm_conv"):
        xbc, window = causal_conv(p, xbc, before, d)
    with scope("ssm_scan"):
        x, bmat, cmat = split_xbc(xbc, d)
        dt, a = step_sizes(p, dt_raw, valid, d)
        if t == 1:
            y, h = ssm_step(x, dt, a, bmat, cmat, h0)
        else:
            y, h = ssd_chunk(x, dt, a, bmat, cmat, h0, min(d["chunk"], t))
        y = y + p["D"].astype(jnp.float32).reshape(a.shape)[..., None] * x.astype(jnp.float32)
    with scope("ssm_gate_norm"):
        y = gate_norm(p, y.reshape(b, t, -1), z, d, eps)
    with scope("ssm_proj"):
        out = y @ p["out_proj"]["kernel"].astype(y.dtype)
    return out, window, h


def attention_dense(p, u, heads, kv_heads, head_dim):
    """Causal softmax attention over whole sequences u [B, T, hidden], grouped
    KV heads, no bias and no position embedding: the module's forward."""
    b, t, _ = u.shape
    mm = lambda name, n: (u @ p[name]["kernel"].astype(u.dtype)).reshape(b, t, n, head_dim)
    q, k, v = mm("q_proj", heads), mm("k_proj", kv_heads), mm("v_proj", kv_heads)
    k, v = jnp.repeat(k, heads // kv_heads, axis=2), jnp.repeat(v, heads // kv_heads, axis=2)
    s = jnp.einsum("btnh,bsnh->bnts", q, k, preferred_element_type=jnp.float32) * head_dim ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, NEG)
    o = jnp.einsum("bnts,bsnh->btnh", jax.nn.softmax(s, axis=-1).astype(u.dtype), v)
    return o.reshape(b, t, -1) @ p["o_proj"]["kernel"].astype(u.dtype)
