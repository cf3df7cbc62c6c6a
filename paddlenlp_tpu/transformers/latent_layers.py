"""Layer mathematics of the latent layer kinds, as plain functions over one
layer's parameter tree: what a whole-sequence module (``transformers/<model>/
modeling.py``, no cache) and the serving step programs
(``experimental/latent_model.py``, paged planes) both compute, from the same code.

Two kinds of layer, named as a configuration's ``layer_kinds()`` yields them:

- ``latent_full``    latent attention (low-rank q and kv chains, one roped key
                     head shared by all) over a learned top-k selection of the
                     cached positions (the indexer);
- ``latent_window``  latent attention with sizes of its own over a window.

Either kind's MLP is dense SwiGLU or sigmoid-routed experts of which this
process holds a share. The expert layer (``route``, ``experts_held``, ``moe``) is
every layer kind's that routes so: the caller says what one expert computes
(``ExpertBody``: ``SWIGLU``, three matrices and SiLU, or ``RELU2``, two and a
squared ReLU), the routing and the held share are the same. The functions:

- ``mla_project``     the q and kv chains of one attention kind: the normed,
                      rescaled q latent, per-head q (nope | roped pe) and the
                      cached row ``(c_kv | roped k_pe)``;
- ``indexer_query`` / ``indexer_key`` / ``index_scores`` / ``kth_largest``
                      the learned selection: which ``index_topk`` cached
                      positions a query may attend;
- ``route``           sigmoid scores in float32, top-k of score + bias over the
                      router's full width, weights normalised over the chosen
                      (or, where ``cfg.scoring_func`` says ``softmax``, softmax
                      scores, their top-k, no bias);
- ``attention_dense`` one attention kind over whole sequences, expanded, causal;
- ``experts_held``    the held experts' part of the result: assignments are
                      sorted by expert into tile-aligned groups and a loop of
                      data-dependent length runs one tile of one expert at a
                      time (the caller's ``ExpertBody``), so no token is
                      dropped and an expert nobody chose is never read;
- ``experts_held_dense`` the same part of the result for a few rows: every held
                      expert on every row in three grouped products;
- ``experts_grouped`` the same part of the result with a backward, for
                      training: assignments sorted by expert, the three
                      products grouped over the held stacks at the rows each
                      expert received (``megablox.gmm``), combined in float32.

What they read of a configuration ``cfg``: ``attention_dims(kind)`` (heads,
q_lora, kv_lora, nope, rope, v, theta, window, s_q, s_kv), ``index_n_heads``,
``index_head_dim``, ``index_topk``, ``qk_rope_head_dim``, ``rms_norm_eps``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``experts_held`` (first,
count) and ``first_k_dense_replace``; the expert layer (``moe``) reads only
``num_experts_per_tok``, ``routed_scaling_factor`` and ``experts_held``."""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

LATENT_FULL, LATENT_WINDOW = "latent_full", "latent_window"
NEG = -1e30

def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotate-half RoPE over the last axis of ``x`` [..., d] at ``positions``
    (broadcast against ``x``'s leading axes), in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def _mm(p, x):
    return x @ p["kernel"].astype(x.dtype)


def swiglu(p, x):
    return _mm(p["down_proj"], jax.nn.silu(_mm(p["gate_proj"], x)) * _mm(p["up_proj"], x))


def relu2(p, x):
    return _mm(p["down_proj"], jnp.square(jax.nn.relu(_mm(p["up_proj"], x))))


def _swiglu_tile(p, e, xs):
    act = jax.nn.silu(xs @ p["gate_proj"][e].astype(xs.dtype)) * (xs @ p["up_proj"][e].astype(xs.dtype))
    return act @ p["down_proj"][e].astype(xs.dtype)


def _relu2_tile(p, e, xs):
    # up_proj is stacked [count, width, hidden] (the checkpoint's own out x in): hidden is a whole number of
    # 128-lane tiles where the published width (1856) is not, so neither stack has a padded minor axis
    up = jnp.einsum("th,wh->tw", xs, p["up_proj"][e].astype(xs.dtype))
    return jnp.square(jax.nn.relu(up)) @ p["down_proj"][e].astype(xs.dtype)


class ExpertBody(NamedTuple):
    """What one expert computes, in the two forms the expert layer needs."""

    tile: Callable  #: (stacked held experts [count, ...], expert number, rows [tile, hidden]) -> rows
    dense: Callable  #: (one expert's ``{proj: {"kernel"}}``, x [..., hidden]) -> [..., hidden]: the shared expert


SWIGLU = ExpertBody(_swiglu_tile, swiglu)  # silu(x W_gate) * (x W_up) W_down
RELU2 = ExpertBody(_relu2_tile, relu2)  # relu(x W_up)^2 W_down, no gate matrix; held up_proj [count, width, hidden]


def mla_project(p, x, positions, d, eps):
    """x [..., hidden] at ``positions`` [...] -> (c_q [..., q_lora], q_nope
    [..., H, nope], q_pe [..., H, rope] roped, row [..., kv_lora + rope]): the
    row is what a latent cache holds for the token, ``(c_kv | roped k_pe)``."""
    c_q = rms_norm(_mm(p["q_a_proj"], x), p["q_a_layernorm"]["scale"], eps) * jnp.asarray(d["s_q"], x.dtype)
    q = _mm(p["q_b_proj"], c_q).reshape(x.shape[:-1] + (d["heads"], d["nope"] + d["rope"]))
    q_nope, q_pe = q[..., : d["nope"]], rope(q[..., d["nope"]:], positions[..., None], d["theta"])
    kv_a = _mm(p["kv_a_proj_with_mqa"], x)
    c_kv = rms_norm(kv_a[..., : d["kv_lora"]], p["kv_a_layernorm"]["scale"], eps) * jnp.asarray(d["s_kv"], x.dtype)
    k_pe = rope(kv_a[..., d["kv_lora"]:], positions, d["theta"])
    return c_q, q_nope, q_pe, jnp.concatenate([c_kv, k_pe], -1)


def kv_b_split(p, d):
    """``kv_b_proj`` as (W^K [kv_lora, H, nope], W^V [kv_lora, H, v])."""
    w = p["kv_b_proj"]["kernel"].reshape(d["kv_lora"], d["heads"], d["nope"] + d["v"])
    return w[..., : d["nope"]], w[..., d["nope"]:]


def indexer_query(p, c_q, x, positions, cfg, theta):
    """(q_I [..., heads, dim] with RoPE on its first ``qk_rope_head_dim`` dims,
    w [..., heads] float32, scaled by heads^-1/2 dim^-1/2)."""
    n, dim, r = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _mm(p["wq_b"], c_q).reshape(x.shape[:-1] + (n, dim))
    q = jnp.concatenate([rope(q[..., :r], positions[..., None], theta), q[..., r:]], -1)
    w = _mm(p["weights_proj"], x).astype(jnp.float32) * (n ** -0.5 * dim ** -0.5)
    return q, w


def indexer_key(p, x, positions, cfg, theta):
    """k_I [..., dim]: LayerNorm(x W_Ik), RoPE on the first ``qk_rope_head_dim`` dims: the indexer plane's row."""
    r = cfg.qk_rope_head_dim
    k = layer_norm(_mm(p["wk"], x), p["k_norm"]["scale"], p["k_norm"]["bias"], cfg.rms_norm_eps)
    return jnp.concatenate([rope(k[..., :r], positions, theta), k[..., r:]], -1)


def index_scores(q_i, w, k_i):
    """I[..., t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s]): inputs in their own
    dtype, products accumulated and everything after them in float32.
    q_i [..., T, n, dim], w [..., T, n], k_i [..., S, dim] -> [..., T, S]."""
    qk = jnp.einsum("...tnd,...sd->...tns", q_i, k_i, preferred_element_type=jnp.float32)
    # highest: on a TPU a float32 product is otherwise rounded to bfloat16 first, and this sum decides a top-k
    return jnp.einsum("...tns,...tn->...ts", jax.nn.relu(qk), w, precision="highest")


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def kth_largest(scores, valid, k):
    """Per row of ``scores`` [..., S], a key such that ``sortable(score) >= key``
    holds for exactly the k largest valid scores (ties with the k-th included;
    every valid score where there are at most k). Exact: the key is built bit
    by bit, each bit one compare-and-count pass, so no sort of S values is made.
    Returns (keys [..., S] uint32 with invalid entries lowest, threshold [..., 1])."""
    keys = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))
    return keys, thr


def route(p, x2d, cfg):
    """x2d [N, hidden] -> (experts [N, k] int32 over the router's full width,
    weights [N, k] float32). Scores are float32 whatever the weights' dtype."""
    logits = jnp.matmul(x2d.astype(jnp.float32), p["gate"]["kernel"].astype(jnp.float32), precision="highest")
    if getattr(cfg, "scoring_func", "sigmoid") == "softmax":
        # softmax over the router's full width, its k largest, no selection bias and no scaling factor
        chosen, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
        return idx.astype(jnp.int32), chosen
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + p["e_score_correction_bias"].astype(jnp.float32), cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx.astype(jnp.int32), chosen / jnp.sum(chosen, -1, keepdims=True) * cfg.routed_scaling_factor


def held_counts(idx, first, count):
    """How many of the routed choices ``idx`` [N, k] fell on each held expert: [count] int32."""
    local = idx - first
    ok = (local >= 0) & (local < count)
    return jnp.sum(jax.nn.one_hot(jnp.where(ok, local, count), count + 1, dtype=jnp.int32), axis=(0, 1))[:count]


def experts_held(p, x2d, idx, w, first, count, live=None, body: ExpertBody = SWIGLU):
    """The held experts' part of ``sum_k w_k E_k(x)``: x2d [N, hidden], idx/w
    [N, k] as ``route`` gives them, experts ``first .. first + count - 1`` held
    in ``p`` ([count, ...] stacked), each computing ``body.tile``. ``live`` [N]
    bool leaves rows out (padding).

    No capacity and no drop: the N*k assignments are ranked within their expert
    and laid into a buffer where each expert's group starts on a tile boundary;
    the loop runs as many tiles as the groups fill (known only on the device),
    each one expert's weights against one tile of rows."""
    n, k = idx.shape
    hidden = x2d.shape[-1]
    tile = 128 if n * k >= 1024 else 16
    local = idx - first
    ok = (local >= 0) & (local < count)
    if live is not None:
        ok &= live[:, None]
    flat = jnp.where(ok, local, count).reshape(-1)  # [A], count = not held here
    onehot = jax.nn.one_hot(flat, count + 1, dtype=jnp.int32)
    sizes = jnp.sum(onehot, 0)[:count]
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot, flat[:, None], 1)[:, 0]
    tiles = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)  # expert e owns tiles [tile_end[e] - tiles[e], tile_end[e])
    group_start = (tile_end - tiles) * tile
    rows = n * k + count * tile  # every assignment held, every group padded: the static bound
    dest = jnp.where(flat < count, jnp.take(group_start, jnp.minimum(flat, count - 1)) + rank, rows)
    token_of_row = jnp.full((rows,), n, jnp.int32).at[dest].set(
        jnp.repeat(jnp.arange(n, dtype=jnp.int32), k), mode="drop")
    x_rows = jnp.concatenate([x2d, jnp.zeros((1, hidden), x2d.dtype)], 0)[token_of_row]

    def one_tile(i, y_rows):
        e = jnp.sum(tile_end <= i).astype(jnp.int32)
        xs = jax.lax.dynamic_slice_in_dim(x_rows, i * tile, tile, 0)
        return jax.lax.dynamic_update_slice_in_dim(y_rows, body.tile(p, e, xs), i * tile, 0)

    y_rows = jax.lax.fori_loop(0, tile_end[-1], one_tile, jnp.zeros((rows, hidden), x2d.dtype))
    picked = y_rows[jnp.minimum(dest, rows - 1)].reshape(n, k, hidden)
    weight = jnp.where(ok, w, 0.0).astype(jnp.float32)
    return jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32), weight).astype(x2d.dtype)


def experts_held_dense(p, x2d, idx, w, first, count, live=None):
    """``experts_held`` for a few rows (a decode pass, a short chunk) of SwiGLU experts: **every held expert on
    every row**, the rows an expert was not chosen for weighted 0. With a hundred rows and sixteen held experts
    nearly every expert is chosen by some row, so the tiles' loop reads the same weights in hundreds of small
    steps a layer (a tile of one expert a turn, its bookkeeping before and after: 26k device operations a pass of
    48 layers, 47 ms where the weights' bytes take 11) where three grouped products read them once; the products'
    rows cost nothing beside the weights' bytes until rows x held experts reaches the thousands. ``p`` holds all
    three matrices stacked [count, width, hidden] (gate_proj and up_proj out x in: handed them as [count, hidden,
    width] the chip's compiler lays the whole stack out anew, 2.25 GB a matrix at 48 x 16 experts)."""
    local = idx - first
    ok = (local >= 0) & (local < count)
    if live is not None:
        ok &= live[:, None]
    # [N, count] float32: the routing weight of each held expert for each row, 0 where it was not chosen
    chosen = jax.nn.one_hot(jnp.where(ok, local, count), count + 1, dtype=jnp.float32)[..., :count]
    share = jnp.einsum("nke,nk->ne", chosen, jnp.where(ok, w, 0.0).astype(jnp.float32))
    wide = lambda name: jnp.einsum("nh,ewh->enw", x2d, p[name].astype(x2d.dtype))
    act = jax.nn.silu(wide("gate_proj")) * wide("up_proj")
    y = jnp.einsum("enw,ewh->enh", act, p["down_proj"].astype(x2d.dtype))
    return jnp.einsum("enh,ne->nh", y.astype(jnp.float32), share).astype(x2d.dtype)


# ------------------------------------------------------------------ the held experts with a backward (training)
GROUPED_COUNTERS = ("expert_assignments", "expert_assignments_local", "expert_tokens_max", "expert_product_rows")


def _gmm_tiling(m, k, n):
    """(rows, contraction, columns) of one grid step of a grouped product: 256 rows
    of one expert against up to 1024 x 1024 of its matrix (a 256 x 1024 x 768 step is
    2 us of MXU work against 0.35 us of step cost; the weights are read once a 256
    rows, at the chip's ridge of 240 FLOP a byte). Small products (tests) in one step."""
    return (256 if m % 256 == 0 else 8), min(k, 1024), min(n, 1024)


def _gmm(x, w, sizes):
    """x [m, k] rows sorted by group, w [count, k, n], sizes [count + 1] (the last group
    is the rows no held expert takes) -> [m, n]: row r of group e times ``w[e]``, zeros
    in the last group. Only the tiles the first ``count`` groups fill are visited (the
    grid's length is computed on the device), and the call has a backward."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return megablox.gmm(x, w.astype(x.dtype), sizes, x.dtype, _gmm_tiling, None, None, False,
                        jax.default_backend() != "tpu")


def _product_rows(sizes, m, count):
    """Rows of one ``_gmm`` call's products: a grid step along the rows covers one row
    tile for one group, so a group costs the tiles from the one its first row lies in
    to the one its last row lies in (``megablox``'s ``make_group_metadata`` counts its
    grid so; tests/transformers/test_deepseek_v3.py holds the two together)."""
    tm = _gmm_tiling(m, 1, 1)[0]
    ends = jnp.cumsum(sizes)[:count]
    starts = ends - sizes[:count]
    return jnp.sum(jnp.where(sizes[:count] > 0, -(-ends // tm) - starts // tm, 0)) * tm


def experts_grouped(p, x2d, idx, w, first, count, total):
    """``experts_held`` with a reverse-mode derivative: the held experts' part of
    ``sum_k w_k E_k(x)`` for SwiGLU experts stacked ``[count, ...]`` in ``p``, and
    what the layer counted (``GROUPED_COUNTERS``, float32 scalars). ``total`` is the
    router's width.

    No capacity and no drop: the N*k assignments are sorted by held expert (those
    no held expert takes last) and walked in chunks of a static number of rows,
    twice the rows an even router would send here; a chunk past the last held
    assignment is skipped (``lax.cond``), so under any imbalance every held
    assignment is computed and memory stays that of one chunk. Inside a chunk the
    three products are grouped: an expert's matrices meet exactly the rows that
    chose it, padded to the kernel's row tile. The weighted rows are added into
    the tokens in float32."""
    n, k = idx.shape
    hidden, a = x2d.shape[-1], n * k
    local = idx - first
    ok = (local >= 0) & (local < count)
    flat = jnp.where(ok, local, count).reshape(-1)  # [A]; count = no held expert
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # assignments by held expert, the others last
    sizes = jnp.sum(jax.nn.one_hot(flat, count + 1, dtype=jnp.int32), 0)  # [count + 1]
    held = a - sizes[count]
    ends = jnp.cumsum(sizes)
    tile = _gmm_tiling(a, 1, 1)[0]
    rows = -(-min(a, -(-2 * a * count // total)) // tile) * tile  # a chunk: twice an even load, whole row tiles
    chunks = -(-a // rows)
    order = jnp.pad(order, (0, chunks * rows - a), constant_values=a)  # past the end: no assignment
    w_flat = jnp.concatenate([jnp.where(ok, w, 0.0).astype(jnp.float32).reshape(-1), jnp.zeros((1,), jnp.float32)])
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, hidden), x2d.dtype)], 0)  # row n: what a padded row reads

    @jax.checkpoint  # the backward needs a chunk's rows again, not every chunk's at once
    def chunk_rows(x_pad, p, w_flat, ends, picks, lo):
        with jax.named_scope("expert_dispatch"):
            token = jnp.minimum(picks // k, n)
            xs = x_pad[token]
            in_chunk = jnp.clip(ends, lo, lo + rows) - lo
            sz = jnp.diff(in_chunk, prepend=0)
            sz = sz.at[count].set(rows - in_chunk[count - 1])  # the chunk's tail: not held, or padding
        with jax.named_scope("expert_mm"):
            act = jax.nn.silu(_gmm(xs, p["gate_proj"], sz)) * _gmm(xs, p["up_proj"], sz)
            ys = _gmm(act, p["down_proj"], sz)
        with jax.named_scope("expert_combine"):
            return ys.astype(jnp.float32) * w_flat[picks][:, None], token, _product_rows(sz, rows, count)

    def one_chunk(carry, c):
        y, visited = carry
        lo = c * rows
        picks = jax.lax.dynamic_slice_in_dim(order, lo, rows)

        def run(y, visited):
            weighted, token, steps = chunk_rows(x_pad, p, w_flat, ends, picks, lo)
            with jax.named_scope("expert_combine"):
                return y.at[token].add(weighted), visited + steps

        return jax.lax.cond(lo < held, run, lambda y, visited: (y, visited), y, visited), None

    init = (jnp.zeros((n + 1, hidden), jnp.float32), jnp.zeros((), jnp.int32))
    (y, visited), _ = jax.lax.scan(one_chunk, init, jnp.arange(chunks, dtype=jnp.int32))
    counters = dict(zip(GROUPED_COUNTERS, (jnp.float32(a), held.astype(jnp.float32),
                                           jnp.max(sizes[:count]).astype(jnp.float32), visited.astype(jnp.float32))))
    return y[:n].astype(x2d.dtype), counters


def moe(p, x, cfg, live=None, body: ExpertBody = SWIGLU):
    """The expert layer on x [..., hidden]: routed part of the held experts plus
    the shared expert, every expert of the form ``body``."""
    x2d = x.reshape(-1, x.shape[-1])
    with jax.named_scope("router"):
        idx, w = route(p, x2d, cfg)
    first, count = cfg.experts_held
    with jax.named_scope("experts"):
        y = experts_held(p["experts"], x2d, idx, w, first, count, live, body)
    with jax.named_scope("shared_expert"):
        y = y + body.dense(p["shared_experts"], x2d)
    return y.reshape(x.shape), idx


def mlp(p, x, cfg, layer, live=None):
    if layer < cfg.first_k_dense_replace:
        with jax.named_scope("mlp"):
            return swiglu(p, x), None
    return moe(p, x, cfg, live)


# ------------------------------------------------------------------ whole-sequence attention (no cache)
def attention_dense(p, x, positions, cfg, kind):
    """One attention kind over whole sequences x [B, T, hidden], causal, in the
    expanded form: the module's forward. Returns (out [B, T, hidden], selected
    [B, T, T] bool: which positions each query attended)."""
    d, eps = cfg.attention_dims(kind), cfg.rms_norm_eps
    b, t, _ = x.shape
    c_q, q_nope, q_pe, row = mla_project(p, x, positions, d, eps)
    c_kv, k_pe = row[..., : d["kv_lora"]], row[..., d["kv_lora"]:]
    w_k, w_v = kv_b_split(p, d)
    k_nope = jnp.einsum("bsc,chn->bshn", c_kv, w_k.astype(x.dtype))
    v = jnp.einsum("bsc,chv->bshv", c_kv, w_v.astype(x.dtype))
    allowed = positions[:, None, :] <= positions[:, :, None]  # [B, T, S]
    if kind == LATENT_WINDOW:
        allowed &= positions[:, None, :] > positions[:, :, None] - d["window"]
    else:
        q_i, w_i = indexer_query(p["indexer"], c_q, x, positions, cfg, d["theta"])
        k_i = indexer_key(p["indexer"], x, positions, cfg, d["theta"])
        keys, thr = kth_largest(index_scores(q_i, w_i, k_i), allowed, cfg.index_topk)
        allowed &= keys >= thr
    s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope, preferred_element_type=jnp.float32)
         + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe, preferred_element_type=jnp.float32))
    s = s * (d["nope"] + d["rope"]) ** -0.5
    prob = jax.nn.softmax(jnp.where(allowed[:, None], s, NEG), axis=-1)
    o = jnp.einsum("bhts,bshv->bthv", prob.astype(x.dtype), v)
    o = o * jax.nn.sigmoid(_mm(p["gate_proj"], x).astype(jnp.float32)).astype(x.dtype)[..., None]
    return _mm(p["o_proj"], o.reshape(b, t, -1)), allowed
