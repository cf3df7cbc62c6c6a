"""Plain reference of the exaone_moe decoder, as ISSUE 35 writes its layers down:
pre-norm residual blocks, ``x <- x + attention(RMSNorm(x))`` then
``x <- x + mlp(RMSNorm(x))``. Layer ``l`` of type ``layer_types[l]``:

- ``q, k, v = u W_q, u W_k, u W_v`` (64 query / 8 KV heads of 128, no biases);
  ``q <- RMSNorm_128(q)``, ``k <- RMSNorm_128(k)`` (one learned scale of 128
  each, shared by the heads); on ``sliding_attention`` layers q and k are
  rotated (rotate-half RoPE over all 128 dims at ``rope_parameters.rope_theta``),
  on ``full_attention`` layers **nothing is rotated**; scores ``q k^T / sqrt(128)``,
  query head ``n`` reading KV head ``n // 8``, causal; on sliding layers key
  ``j`` is visible to query ``t`` iff ``0 <= t - j < sliding_window`` (**the
  window as an explicit mask** over all keys); softmax; ``W_o``.
- the first ``first_k_dense_replace`` layers: a dense SwiGLU MLP. The rest:
  ``s = sigmoid(u W_r)`` in float32 over the router's full width, top-k of
  ``s + bias``, weights ``s`` of the chosen, normalised, times
  ``routed_scaling_factor``; every expert a SwiGLU; plus one shared expert on
  every token. The chip's share: ``num_experts`` experts from
  ``first_held_expert`` are held, routing and the normalising sum are over all
  ``num_experts_total``, only held experts' terms are added.

The residual form is the configuration file's first ``assumed`` entry; it lives
in ``layer_forward`` alone. Straight ``jax.numpy`` in float32 under ``highest``
matmul precision: no kernel, no cache, no batching, nothing imported from the
program. Attention runs a block of queries at a time (their q rows projected,
normed and rotated inside the block, their output through ``W_o`` at once) and
the MLPs a block of tokens at a time, only so that 17.4k tokens fit beside the
resident engine; each expert runs on the tokens that chose it, its weights made
from the seed one expert at a time.

Weights come from ``--seed``: a leaf depends on (seed, layer, leaf name), an
expert's on (seed, layer, the expert's number in the whole model), so the
shares of one seed are the parts of one model. ``program_params`` lays the
same numbers into the program's parameter tree.

Precisions (``precision=``): ``"float32"`` the reference proper; ``"int8"`` the
serving control: every projection's weight rounded to int8 per output channel
and its input per row (the router stays float32). The limits are data of the
configuration (``bench.limits`` in bench/configs/k-exaone-serve-ep16.json), with
the chip readings behind them in that file's ``limits_note``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FULL, WINDOW = "full_attention", "sliding_attention"
BIAS_STD = 0.05  # of the seeded e_score_correction_bias
NORM_LEAVES = ("ln1", "ln2", "q_norm", "k_norm", "norm")  # float32, 1 + normal / 8


# ------------------------------------------------------------------ sizes
def held(cfg):
    return cfg.get("first_held_expert", 0), cfg["num_experts"]


def router_width(cfg):
    return cfg.get("num_experts_total") or cfg["num_experts"]


def is_dense(cfg, layer):
    return layer < cfg["first_k_dense_replace"]


def leaf_shapes(cfg, layer):
    """{leaf: shape} of one layer, without its routed experts."""
    hidden, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    out = {"ln1": (hidden,), "ln2": (hidden,), "q": (hidden, q), "k": (hidden, kv), "v": (hidden, kv),
           "o": (q, hidden), "q_norm": (hd,), "k_norm": (hd,)}
    if is_dense(cfg, layer):
        width = cfg["intermediate_size"]
        out.update({"gate": (hidden, width), "up": (hidden, width), "down": (width, hidden)})
    else:
        width = cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
        out.update({"router": (hidden, router_width(cfg)), "router_bias": (router_width(cfg),),
                    "sh_gate": (hidden, width), "sh_up": (hidden, width), "sh_down": (width, hidden)})
    return out


# ------------------------------------------------------------------ weights
def _base_key(seed):
    return jax.random.key(seed % (2**31 - 1) if isinstance(seed, int) else seed)


def seed_array(seed):
    return jnp.asarray(seed % (2**31 - 1), jnp.uint32)


def layer_key(seed, layer):
    return jax.random.fold_in(_base_key(seed), layer + 1)


def _draw(cfg, key, name, shape, dtype):
    """float32: norm scales (1 + normal / 8) and the router's bias (normal x 0.05);
    in the weights' dtype: every matrix (normal x initializer_range)."""
    draw = jax.random.normal(key, shape, jnp.float32)
    if name in NORM_LEAVES:
        return 1.0 + 0.125 * draw  # a power of two: the product is exact
    if name == "router_bias":
        return BIAS_STD * draw
    return (cfg["initializer_range"] * draw).astype(dtype)


def layer_weights(cfg, seed, layer, dtype):
    """One layer's weights but its routed experts; ``layer`` is a Python int."""
    key, shapes = layer_key(seed, layer), leaf_shapes(cfg, layer)
    return {n: _draw(cfg, jax.random.fold_in(key, i), n, shapes[n], dtype) for i, n in enumerate(sorted(shapes))}


def expert_weights(cfg, seed, layer, expert, dtype):
    """Routed expert ``expert`` (its number in the whole model; may be traced) of ``layer``."""
    key = jax.random.fold_in(jax.random.fold_in(layer_key(seed, layer), 1000), expert)
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    shapes = {"gate": (hidden, width), "up": (hidden, width), "down": (width, hidden)}
    return {n: _draw(cfg, jax.random.fold_in(key, i), "expert", s, dtype) for i, (n, s) in enumerate(shapes.items())}


def global_weights(cfg, seed, dtype):
    key = jax.random.fold_in(_base_key(seed), 0)
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return {"embed": _draw(cfg, jax.random.fold_in(key, 0), "embed", shape, dtype),
            "norm": _draw(cfg, jax.random.fold_in(key, 1), "norm", (cfg["hidden_size"],), dtype),
            "head": _draw(cfg, jax.random.fold_in(key, 2), "head", shape[::-1], dtype)}


PROGRAM_LEAF = {  # reference leaf -> path under the program's ``model/layers_<i>``
    "ln1": ("input_layernorm", "scale"), "ln2": ("post_attention_layernorm", "scale"),
    "q": ("self_attn", "q_proj", "kernel"), "k": ("self_attn", "k_proj", "kernel"),
    "v": ("self_attn", "v_proj", "kernel"), "o": ("self_attn", "o_proj", "kernel"),
    "q_norm": ("self_attn", "q_norm", "scale"), "k_norm": ("self_attn", "k_norm", "scale"),
    "gate": ("mlp", "gate_proj", "kernel"), "up": ("mlp", "up_proj", "kernel"), "down": ("mlp", "down_proj", "kernel"),
    "router": ("mlp", "gate", "kernel"), "router_bias": ("mlp", "e_score_correction_bias"),
    "sh_gate": ("mlp", "shared_experts", "gate_proj", "kernel"), "sh_up": ("mlp", "shared_experts", "up_proj", "kernel"),
    "sh_down": ("mlp", "shared_experts", "down_proj", "kernel"),
}
EXPERT_LEAF = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}


def _put(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def program_params(cfg, seed, dtype):
    """The same numbers in the program's parameter tree (unrolled
    ``model/layers_<i>``, the held experts stacked on a leading axis)."""
    g = global_weights(cfg, seed, dtype)
    model = {"embed_tokens": {"embedding": g["embed"]}, "norm": {"scale": g["norm"]}}
    first, count = held(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        tree = model.setdefault(f"layers_{layer}", {})
        for name, value in layer_weights(cfg, seed, layer, dtype).items():
            _put(tree, PROGRAM_LEAF[name], value)
        if not is_dense(cfg, layer):
            stacked = jax.lax.map(lambda e: expert_weights(cfg, seed, layer, e, dtype),
                                  first + jnp.arange(count, dtype=jnp.int32))
            for name, value in stacked.items():
                _put(tree, ("mlp", "experts", EXPERT_LEAF[name]), value)
    return {"model": model, "lm_head": {"kernel": g["head"]}}


# ------------------------------------------------------------------ forward
def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _matmul(x, w, precision):
    w = w.astype(jnp.float32)
    if precision == "int8":
        return jnp.matmul(_fake_int8(x, -1), _fake_int8(w, 0), precision="highest")
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE of x [T, ..., d] (d even) at positions ``pos`` [T]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1) * sin


def _blocks(t, most):
    """A block length that divides ``t``, at most ``most``."""
    b = min(t, most)
    while t % b:
        b -= 1
    return b


def attention(cfg, kind, w, x, precision="float32", q_block=64, window=None, rotate=None):
    """One layer's attention on one sequence: x [T, hidden] normed input at
    positions 0..T-1 -> [T, hidden]. ``window`` and ``rotate`` default to what
    the layer type says (the tests pass others: a window one off, rotation on
    a full layer)."""
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    if window is None:
        window = cfg["sliding_window"] if kind == WINDOW else None
    if rotate is None:
        rotate = kind == WINDOW
    pos = jnp.arange(t)
    k = _rmsnorm(_matmul(x, w["k"], precision).reshape(t, kv, hd), w["k_norm"], eps)
    v = _matmul(x, w["v"], precision).reshape(t, kv, hd)
    if rotate:
        k = _rope(k, pos, theta)
    bq = _blocks(t, q_block)

    def block(s0):
        rows = s0 + jnp.arange(bq)
        q = _rmsnorm(_matmul(x[rows], w["q"], precision).reshape(bq, kv, heads // kv, hd), w["q_norm"], eps)
        if rotate:
            q = _rope(q, rows, theta)
        s = jnp.einsum("tgrh,sgh->grts", q, k, precision="highest") * hd ** -0.5
        seen = pos[None, :] <= rows[:, None]
        if window is not None:
            seen &= pos[None, :] > rows[:, None] - window
        o = jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1), v,
                       precision="highest")
        return _matmul(o.reshape(bq, heads * hd), w["o"], precision)

    return jax.lax.map(block, jnp.arange(0, t, bq)).reshape(t, -1)


def _swiglu(x, gate, up, down, precision, most=1024):
    """SwiGLU on x [T, hidden], a block of tokens at a time."""
    t = x.shape[0]
    b = _blocks(t, most)
    one = lambda xs: _matmul(jax.nn.silu(_matmul(xs, gate, precision)) * _matmul(xs, up, precision), down, precision)
    return one(x) if b == t else jax.lax.map(one, x.reshape(t // b, b, -1)).reshape(t, -1)


def route(cfg, w, x):
    """(chosen experts [T, k] over the router's full width, weights [T, k]); float32 whatever the precision."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"].astype(jnp.float32), precision="highest"))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, chosen / chosen.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def held_experts(cfg, seed, layer, weight_dtype, experts=None):
    """The held experts' (or ``experts = (first, count)``) weights of ``layer``, made one expert at a time from the
    seed and stacked [count, ...] in the weights' dtype: what ``routed_part`` draws itself where it is not handed them."""
    first, count = experts if experts is not None else held(cfg)
    return _held_experts(_Frozen(cfg), seed_array(seed) if isinstance(seed, int) else seed,
                         jnp.asarray(layer, jnp.int32), jnp.dtype(weight_dtype).name, first, count)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _held_experts(cfg, seed, layer, weight_dtype, first, count):
    return jax.lax.map(lambda e: expert_weights(cfg, seed, layer, e, jnp.dtype(weight_dtype)),
                       first + jnp.arange(count, dtype=jnp.int32))


def routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision="float32", experts=None, drawn=None):
    """sum over the held experts (or ``experts = (first, count)``) of w_k E_k(x)
    for the tokens that chose them; x [T, hidden]. Each expert runs on its own
    tokens only: their count is read back, and rounded up to a bucket.
    ``drawn`` (``held_experts``) spares the draw where several sequences pass one layer."""
    first, count = experts if experts is not None else held(cfg)
    t = x.shape[0]
    sizes = np.asarray(jnp.sum(idx[:, :, None] == (first + jnp.arange(count))[None, None, :], axis=(0, 1)))
    cap = max(64, t // 8)  # twice an even share of the router's choices; doubled where an expert drew more
    while cap < sizes.max(initial=0):
        cap *= 2
    if drawn is None:
        drawn = held_experts(cfg, seed, layer, weight_dtype, (first, count))
    return _routed_part(drawn, idx, wts, x, precision, first, min(cap, t))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _routed_part(drawn, idx, wts, x, precision, first, cap):
    t = x.shape[0]

    def one(out, at):
        e, w = at
        mine = idx == e  # [T, k]
        weight = jnp.sum(jnp.where(mine, wts, 0.0), -1)
        rows = jnp.nonzero(mine.any(-1), size=cap, fill_value=t)[0]
        xs = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], 0)[rows]
        y = _swiglu(xs, w["gate"], w["up"], w["down"], precision)
        scale = jnp.concatenate([weight, jnp.zeros((1,), weight.dtype)], 0)[rows]
        return out.at[rows].add(y * scale[:, None], mode="drop"), None

    count = drawn["gate"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (first + jnp.arange(count, dtype=jnp.int32), drawn))
    return out


def mlp(cfg, seed, layer, w, x, weight_dtype, precision="float32"):
    """The MLP of ``layer`` on x [T, hidden] (normed input): dense, or routed + shared."""
    if is_dense(cfg, layer):
        return _swiglu(x, w["gate"], w["up"], w["down"], precision)
    idx, wts = route(cfg, w, x)
    return (routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision)
            + _swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"], precision))


class _Frozen(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _attn_step(cfg, kind, w, h, precision):
    """The residual form, first half: (h + attention(RMSNorm(h)), the MLP's normed input)."""
    x = _rmsnorm(h, w["ln1"], cfg["rms_norm_eps"])
    h = h + attention(cfg, kind, w, x, precision)
    return h, _rmsnorm(h, w["ln2"], cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _layer_weights(cfg, layer, seed, weight_dtype):
    """In the weights' own dtype (``_matmul`` widens one matrix at a time: the dense layer is 1.8 GB in float32)."""
    return layer_weights(cfg, seed, layer, jnp.dtype(weight_dtype))


_dense_or_shared = jax.jit(
    lambda dense, w, x, precision: _swiglu(x, w["gate"], w["up"], w["down"], precision) if dense
    else _swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"], precision), static_argnums=(0, 3))
_route = jax.jit(route, static_argnums=(0,))


def layer_forward(cfg, seed, layer, w, h, weight_dtype, precision="float32", drawn=None):
    """One decoder layer on one sequence ``h`` [T, hidden], causal; ``w`` from ``layer_weights``."""
    cfg = _Frozen(cfg)
    h, x = _attn_step(cfg, cfg["layer_types"][layer], w, h, precision)
    y = _dense_or_shared(is_dense(cfg, layer), w, x, precision)
    if not is_dense(cfg, layer):
        idx, wts = _route(cfg, w, x)
        y = y + routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision, drawn=drawn)
    return h + y


def head_logits(cfg, g, h, precision="float32"):
    """Final norm and head for the rows of ``h`` [N, hidden] -> [N, vocab] float32."""
    return _matmul(_rmsnorm(h, g["norm"], cfg["rms_norm_eps"]), g["head"], precision)


def forward(cfg, seed, ids, weight_dtype="float32", precision="float32"):
    """Whole forward of one sequence of token ids -> logits [T, vocab] (tests and small sizes)."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    g = {k: v.astype(jnp.float32) for k, v in global_weights(cfg, seed, jnp.dtype(weight_dtype)).items()}
    h = g["embed"][jnp.asarray(ids)]
    for layer in range(cfg["num_hidden_layers"]):
        w = _layer_weights(_Frozen(cfg), layer, seed_array(seed), jnp.dtype(weight_dtype).name)
        h = layer_forward(cfg, seed, layer, w, h, weight_dtype, precision)
    return head_logits(cfg, g, h, precision)


# ------------------------------------------------------------------ serving check
_BUCKETS = (512, 1024, 2048, 4096, 8192, 12288, 17408)
_SERVED_BUCKETS = (64, 256, 768)
_TOKENS_A_GROUP = 49152  # padded positions whose float32 hidden states stay on the device at once: 1.2 GB


def served_gaps(cfg, seed, sequences, weight_dtype, control=None):
    """The serving comparison, as ``dense_decoder.served_gaps``: ``sequences`` is a
    list of (prompt ids, served ids); each runs through the reference once,
    teacher-forced, one sequence and one layer at a time (a layer's weights are
    drawn once for a group of sequences whose hidden states fit the device
    together); returned per sequence is the gap by which each served token's
    logit lies below the reference's best and, with ``control``, the gap under
    the reference of the token that precision puts first at the same positions."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    frozen = _Frozen(cfg)
    seed_a = seed_array(seed)
    dtype_name = jnp.dtype(weight_dtype).name
    g = jax.jit(lambda s: global_weights(cfg, s, jnp.dtype(weight_dtype)))(seed_a)

    def gaps_at(g, h_ref, h_low, lo, tok, control):
        rows = jnp.clip(lo + jnp.arange(tok.shape[0]), 0, h_ref.shape[0] - 1)
        ref = head_logits(frozen, g, h_ref[rows], "float32")
        best = ref.max(-1)
        own = best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        if control is None:
            return own, own
        low = head_logits(frozen, g, h_low[rows], control).argmax(-1)
        return own, best - jnp.take_along_axis(ref, low[:, None], -1)[:, 0]

    gaps_at = jax.jit(gaps_at, static_argnums=(5,))
    embed = jax.jit(lambda g, ids: g["embed"][ids].astype(jnp.float32))
    precisions = ("float32", control) if control else ("float32",)
    fed = [np.asarray(list(p) + list(sv[:-1]), np.int32) for p, sv in sequences]
    bucket_of = [next((b for b in _BUCKETS if b >= len(ids)), len(ids)) for ids in fed]
    groups, room = [], 0
    for i in sorted(range(len(fed)), key=lambda i: bucket_of[i]):
        if not groups or room < bucket_of[i]:
            groups.append([])
            room = _TOKENS_A_GROUP // len(precisions)
        groups[-1].append(i)
        room -= bucket_of[i]
    out = [None] * len(sequences)
    for group in groups:
        runs = {}
        for i in group:
            padded = np.zeros(bucket_of[i], np.int32)
            padded[: len(fed[i])] = fed[i]
            first = embed(g, jnp.asarray(padded))
            runs[i] = {p: first for p in precisions}
        for layer in range(cfg["num_hidden_layers"]):
            w = _layer_weights(frozen, layer, seed_a, dtype_name)
            drawn = None if is_dense(cfg, layer) else held_experts(cfg, seed_a, layer, weight_dtype)
            for i in group:
                for precision in precisions:
                    runs[i][precision] = layer_forward(cfg, seed_a, layer, w, runs[i][precision], weight_dtype,
                                                       precision, drawn)
            del w, drawn
        for i in group:
            prompt, served = sequences[i]
            lo, n = len(prompt) - 1, len(served)
            tok = np.zeros(next((b for b in _SERVED_BUCKETS if b >= n), n), np.int32)
            tok[:n] = served
            own, low = gaps_at(g, runs[i]["float32"], runs[i][control] if control else runs[i]["float32"],
                               jnp.asarray(lo, jnp.int32), jnp.asarray(tok), control)
            out[i] = {"gaps": np.asarray(own)[:n]}
            if control:
                out[i]["control_gaps"] = np.asarray(low)[:n]
            del runs[i]
    return out
