"""Plain reference of the dots3_note text decoder, as ISSUE 26 writes its layers
down: pre-norm residual blocks; latent attention (MLA) of two kinds picked by
``layer_types`` (full layers with a learned top-k indexer, window layers with
their own sizes), a headwise sigmoid gate on the attention output, a dense
SwiGLU MLP in the leading layers and sigmoid-routed experts (selection bias,
top-k over the router's full width, one shared expert) in the rest. The chip's
share: ``n_routed_experts`` experts starting at ``first_held_expert`` are held,
routing and the normalising sum are over all ``n_routed_experts_total``, only
held experts' terms are added. Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no batching,
nothing imported from the program. Attention runs in blocks of queries (and
groups of heads) only so that 16.8k tokens fit beside the engine; each expert
runs on the tokens that chose it.

Weights come from ``--seed``: a leaf depends on (seed, layer, leaf name), an
expert's on (seed, layer, the expert's number in the whole model), so the
shares of one seed are the parts of one model. ``program_params`` lays the
same numbers into the program's parameter tree.

Precisions (``precision=``): ``"float32"`` the reference proper; ``"int8"``
the serving control: every projection's weight rounded to int8 per output
channel and its input per row. The limits and the chip readings behind them:
bottom of this file."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FULL, WINDOW = "full_attention", "sliding_attention"
BIAS_STD = 0.05  # of the seeded e_score_correction_bias (scores spread about 0.25 under seeded weights)


# ------------------------------------------------------------------ sizes
def dims(cfg, kind):
    pre = "" if kind == FULL else "swa_"
    heads = cfg["num_attention_heads" if kind == FULL else "swa_num_attention_heads"]
    d = {"heads": heads, "q_lora": cfg[pre + "q_lora_rank"], "kv_lora": cfg[pre + "kv_lora_rank"],
         "nope": cfg[pre + "qk_nope_head_dim"], "rope": cfg[pre + "qk_rope_head_dim"], "v": cfg[pre + "v_head_dim"],
         "theta": float(cfg[pre + "rope_theta"]), "window": cfg["sliding_window_size"] if kind == WINDOW else None}
    rescale = cfg.get("apply_mla_qkv_lora_rescale", True)
    d["s_q"] = (cfg["hidden_size"] / d["q_lora"]) ** 0.5 if rescale else 1.0
    d["s_kv"] = (cfg["hidden_size"] / d["kv_lora"]) ** 0.5 if rescale else 1.0
    return d


def held(cfg):
    return cfg.get("first_held_expert", 0), cfg["n_routed_experts"]


def router_width(cfg):
    return cfg.get("n_routed_experts_total") or cfg["n_routed_experts"]


def is_dense(cfg, layer):
    return layer < cfg["first_k_dense_replace"]


def leaf_shapes(cfg, layer):
    """{leaf: shape} of one layer, without its routed experts."""
    kind, hidden = cfg["layer_types"][layer], cfg["hidden_size"]
    d = dims(cfg, kind)
    h = d["heads"]
    out = {"ln1": (hidden,), "ln2": (hidden,), "q_a": (hidden, d["q_lora"]), "q_a_ln": (d["q_lora"],),
           "q_b": (d["q_lora"], h * (d["nope"] + d["rope"])), "kv_a": (hidden, d["kv_lora"] + d["rope"]),
           "kv_a_ln": (d["kv_lora"],), "kv_b": (d["kv_lora"], h * (d["nope"] + d["v"])),
           "o": (h * d["v"], hidden), "attn_gate": (hidden, h)}
    if kind == FULL:
        n, dim = cfg["index_n_heads"], cfg["index_head_dim"]
        out.update({"idx_q": (d["q_lora"], n * dim), "idx_k": (hidden, dim), "idx_k_ln": (dim,),
                    "idx_k_ln_b": (dim,), "idx_w": (hidden, n)})
    if is_dense(cfg, layer):
        width = cfg["intermediate_size"]
        out.update({"gate": (hidden, width), "up": (hidden, width), "down": (width, hidden)})
    else:
        width = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
        out.update({"router": (hidden, router_width(cfg)), "router_bias": (router_width(cfg),),
                    "sh_gate": (hidden, width), "sh_up": (hidden, width), "sh_down": (width, hidden)})
    return out


NORM_LEAVES = ("ln1", "ln2", "q_a_ln", "kv_a_ln", "idx_k_ln")  # scales: 1 + normal / 8, float32
SMALL_LEAVES = {"idx_k_ln_b": 0.02, "router_bias": BIAS_STD}  # float32, normal times this


# ------------------------------------------------------------------ weights
def _base_key(seed):
    return jax.random.key(seed % (2**31 - 1) if isinstance(seed, int) else seed)


def seed_array(seed):
    return jnp.asarray(seed % (2**31 - 1), jnp.uint32)


def layer_key(seed, layer):
    return jax.random.fold_in(_base_key(seed), layer + 1)


def _draw(cfg, key, name, shape, dtype):
    draw = jax.random.normal(key, shape, jnp.float32)
    if name in NORM_LEAVES or name == "norm":
        return 1.0 + 0.125 * draw  # a power of two: the product is exact
    if name in SMALL_LEAVES:
        return SMALL_LEAVES[name] * draw
    return (cfg["initializer_range"] * draw).astype(dtype)


def layer_weights(cfg, seed, layer, dtype):
    """One layer's weights but its routed experts; ``layer`` is a Python int."""
    key = layer_key(seed, layer)
    names = sorted(leaf_shapes(cfg, layer))
    return {n: _draw(cfg, jax.random.fold_in(key, i), n, leaf_shapes(cfg, layer)[n], dtype)
            for i, n in enumerate(names)}


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
    "q_a": ("self_attn", "q_a_proj", "kernel"), "q_a_ln": ("self_attn", "q_a_layernorm", "scale"),
    "q_b": ("self_attn", "q_b_proj", "kernel"), "kv_a": ("self_attn", "kv_a_proj_with_mqa", "kernel"),
    "kv_a_ln": ("self_attn", "kv_a_layernorm", "scale"), "kv_b": ("self_attn", "kv_b_proj", "kernel"),
    "o": ("self_attn", "o_proj", "kernel"), "attn_gate": ("self_attn", "gate_proj", "kernel"),
    "idx_q": ("self_attn", "indexer", "wq_b", "kernel"), "idx_k": ("self_attn", "indexer", "wk", "kernel"),
    "idx_k_ln": ("self_attn", "indexer", "k_norm", "scale"), "idx_k_ln_b": ("self_attn", "indexer", "k_norm", "bias"),
    "idx_w": ("self_attn", "indexer", "weights_proj", "kernel"),
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


def program_leaves(tree, layer):
    """{reference leaf name: array} of one layer out of a tree shaped like ``program_params``."""
    out = {}
    for name, path in PROGRAM_LEAF.items():
        node = tree["model"][f"layers_{layer}"]
        for p in path:
            node = node.get(p) if isinstance(node, dict) else None
            if node is None:
                break
        if node is not None:
            out[name] = node
    return out


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


def _layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


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


def _kth_largest(values, k):
    """Per row of values [R, S]: the k-th largest (the smallest where the row has fewer than k finite ones)."""
    s = values.shape[-1]
    if s <= k:
        return jnp.full(values.shape[:-1] + (1,), -jnp.inf)
    return jnp.sort(values, axis=-1)[:, s - k: s - k + 1]


def attention(cfg, kind, w, x, precision="float32", q_block=64, head_group=16):
    """One attention kind on one sequence: x [T, hidden] normed input at positions
    0..T-1 -> (out [T, hidden], selected [T, T] bool or None for a window layer).
    Heads run a group at a time and queries a block at a time, each group's
    output going through its rows of the output projection at once, so that
    16.8k tokens fit in 3 GB; the sums are the same, in another order."""
    d, eps, t = dims(cfg, kind), cfg["rms_norm_eps"], x.shape[0]
    mm = functools.partial(_matmul, precision=precision)
    pos = jnp.arange(t)
    heads, nope, rp, dv = d["heads"], d["nope"], d["rope"], d["v"]
    c_q = d["s_q"] * _rmsnorm(mm(x, w["q_a"]), w["q_a_ln"], eps)
    kv_a = mm(x, w["kv_a"])
    c_kv = d["s_kv"] * _rmsnorm(kv_a[:, : d["kv_lora"]], w["kv_a_ln"], eps)
    k_pe = _rope(kv_a[:, d["kv_lora"]:], pos, d["theta"])
    gate = jax.nn.sigmoid(mm(x, w["attn_gate"]))  # [T, heads]
    q_b = w["q_b"].reshape(d["q_lora"], heads, nope + rp)
    kv_b = w["kv_b"].astype(jnp.float32).reshape(d["kv_lora"], heads, nope + dv)
    w_o = w["o"].reshape(heads, dv, -1)
    bq = _blocks(t, q_block)
    starts = jnp.arange(0, t, bq)

    if kind == FULL:
        n, dim, r = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
        q_i = mm(c_q, w["idx_q"]).reshape(t, n, dim)
        q_i = jnp.concatenate([_rope(q_i[..., :r], pos, d["theta"]), q_i[..., r:]], -1)
        k_i = _layernorm(mm(x, w["idx_k"]), w["idx_k_ln"], w["idx_k_ln_b"], eps)
        k_i = jnp.concatenate([_rope(k_i[:, :r], pos, d["theta"]), k_i[:, r:]], -1)
        w_i = mm(x, w["idx_w"]) * (n ** -0.5 * dim ** -0.5)

        def select(s0):
            rows = s0 + jnp.arange(bq)
            qk = jnp.einsum("tnd,sd->tns", q_i[rows], k_i, precision="highest")
            score = jnp.einsum("tns,tn->ts", jax.nn.relu(qk), w_i[rows], precision="highest")
            causal = pos[None, :] <= rows[:, None]
            score = jnp.where(causal, score, -jnp.inf)
            return causal & (score >= _kth_largest(score, cfg["index_topk"]))

        allowed = jax.lax.map(select, starts).reshape(t, t)
    else:
        allowed = None

    # keys a block of queries can see: all of them (full), or the window behind the block
    span = t if kind == FULL else min(t, bq + d["window"] - 1)
    groups = heads // head_group if heads % head_group == 0 else 1
    hg = heads // groups
    per_group = (
        q_b.reshape(d["q_lora"], groups, hg, nope + rp).transpose(1, 0, 2, 3),
        kv_b.reshape(d["kv_lora"], groups, hg, nope + dv).transpose(1, 0, 2, 3),
        w_o.reshape(groups, hg * dv, -1), gate.reshape(t, groups, hg).transpose(1, 0, 2))

    def group(out, xs):
        q_b_g, kv_b_g, w_o_g, gate_g = xs
        q = mm(c_q, q_b_g.reshape(d["q_lora"], -1)).reshape(t, hg, nope + rp)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, d["theta"])], -1)
        k_nope = jnp.einsum("sc,chn->shn", c_kv, kv_b_g[..., :nope], precision="highest")
        v = jnp.einsum("sc,chv->shv", c_kv, kv_b_g[..., nope:], precision="highest")

        def block(s0):
            rows = s0 + jnp.arange(bq)
            k0 = jnp.clip(s0 + bq - span, 0, t - span)
            keys = k0 + jnp.arange(span)
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, k0, span, 0)
            qb = q[rows]
            s = (jnp.einsum("thn,shn->hts", qb[..., :nope], cut(k_nope), precision="highest")
                 + jnp.einsum("thr,sr->hts", qb[..., nope:], cut(k_pe), precision="highest")) * (nope + rp) ** -0.5
            if kind == FULL:
                mask = allowed[rows]
            else:
                mask = (keys[None, :] <= rows[:, None]) & (keys[None, :] > rows[:, None] - d["window"])
            p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
            return jnp.einsum("hts,shv->thv", p, cut(v), precision="highest")

        o = jax.lax.map(block, starts).reshape(t, hg, dv) * gate_g[:, :, None]
        return out + mm(o.reshape(t, -1), w_o_g), None

    out, _ = jax.lax.scan(group, jnp.zeros((t, w["o"].shape[-1]), jnp.float32), per_group)
    return out, allowed


def _swiglu(x, gate, up, down, precision):
    return _matmul(jax.nn.silu(_matmul(x, gate, precision)) * _matmul(x, up, precision), down, precision)


def route(cfg, w, x):
    """(chosen experts [T, k] over the router's full width, weights [T, k]); float32 whatever the precision."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"].astype(jnp.float32), precision="highest"))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, chosen / chosen.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision="float32", experts=None):
    """sum over the held experts (or ``experts = (first, count)``) of w_k E_k(x)
    for the tokens that chose them; x [T, hidden]. Each expert runs on its own
    tokens only: their count is read back, and rounded up to a bucket."""
    first, count = experts if experts is not None else held(cfg)
    t = x.shape[0]
    sizes = np.asarray(jnp.sum(idx[:, :, None] == (first + jnp.arange(count))[None, None, :], axis=(0, 1)))
    cap = max(64, t // 16)  # twice an even share of the router's choices; doubled where an expert drew more
    while cap < sizes.max(initial=0):
        cap *= 2
    return _routed_part(_Frozen(cfg), seed_array(seed) if isinstance(seed, int) else seed,
                        jnp.asarray(layer, jnp.int32), idx, wts, x, jnp.dtype(weight_dtype).name, precision,
                        first, count, min(cap, t))


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9, 10))
def _routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision, first, count, cap):
    t = x.shape[0]

    def one(out, e):
        w = {k: v.astype(jnp.float32) for k, v in expert_weights(cfg, seed, layer, e, jnp.dtype(weight_dtype)).items()}
        mine = idx == e  # [T, k]
        weight = jnp.sum(jnp.where(mine, wts, 0.0), -1)
        rows = jnp.nonzero(mine.any(-1), size=cap, fill_value=t)[0]
        xs = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], 0)[rows]
        y = _swiglu(xs, w["gate"], w["up"], w["down"], precision)
        scale = jnp.concatenate([weight, jnp.zeros((1,), weight.dtype)], 0)[rows]
        return out.at[rows].add(y * scale[:, None], mode="drop"), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), first + jnp.arange(count, dtype=jnp.int32))
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
    x = _rmsnorm(h, w["ln1"], cfg["rms_norm_eps"])
    h = h + attention(cfg, kind, w, x, precision)[0]
    return h, _rmsnorm(h, w["ln2"], cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _layer_weights32(cfg, layer, seed, weight_dtype):
    return {k: v.astype(jnp.float32) for k, v in layer_weights(cfg, seed, layer, jnp.dtype(weight_dtype)).items()}


_dense_or_shared = jax.jit(
    lambda dense, w, x, precision: _swiglu(x, w["gate"], w["up"], w["down"], precision) if dense
    else _swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"], precision), static_argnums=(0, 3))
_route = jax.jit(route, static_argnums=(0,))


def layer_forward(cfg, seed, layer, w, h, weight_dtype, precision="float32"):
    """One decoder layer on one sequence ``h`` [T, hidden], causal; ``w`` from ``layer_weights`` in float32."""
    cfg = _Frozen(cfg)
    h, x = _attn_step(cfg, cfg["layer_types"][layer], w, h, precision)
    y = _dense_or_shared(is_dense(cfg, layer), w, x, precision)
    if not is_dense(cfg, layer):
        idx, wts = _route(cfg, w, x)
        y = y + routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision)
    return h + y


def head_logits(cfg, g, h, precision="float32"):
    """Final norm and head for the rows of ``h`` [N, hidden] -> [N, vocab] float32."""
    return _matmul(_rmsnorm(h, g["norm"], cfg["rms_norm_eps"]), g["head"], precision)


def forward(cfg, seed, ids, weight_dtype="float32", precision="float32"):
    """Whole forward of one sequence of token ids -> logits [T, vocab] (tests and small sizes)."""
    g = {k: v.astype(jnp.float32) for k, v in global_weights(cfg, seed, jnp.dtype(weight_dtype)).items()}
    h = g["embed"][jnp.asarray(ids)]
    for layer in range(cfg["num_hidden_layers"]):
        w = _layer_weights32(_Frozen(cfg), layer, seed_array(seed), jnp.dtype(weight_dtype).name)
        h = layer_forward(cfg, seed, layer, w, h, weight_dtype, precision)
    return head_logits(cfg, g, h, precision)


# ------------------------------------------------------------------ serving check
_BUCKETS = (64, 512, 4096, 8192, 12288, 16896)


def served_gaps(cfg, seed, sequences, weight_dtype, control=None):
    """The serving comparison, as ``dense_decoder.served_gaps``: ``sequences`` is a
    list of (prompt ids, served ids); each runs through the reference once,
    teacher-forced, one sequence and one layer at a time; returned per sequence
    is the gap by which each served token's logit lies below the reference's
    best and, with ``control``, the gap under the reference of the token that
    precision puts first at the same positions."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    frozen = _Frozen(cfg)
    seed_a = seed_array(seed)
    dtype_name = jnp.dtype(weight_dtype).name
    g = jax.jit(lambda s: {k: v.astype(jnp.float32) for k, v in
                           global_weights(cfg, s, jnp.dtype(weight_dtype)).items()})(seed_a)

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
    out = []
    for prompt, served in sequences:
        ids = np.asarray(list(prompt) + list(served[:-1]), np.int32)
        padded = np.zeros(next(b for b in _BUCKETS if b >= len(ids)), np.int32)
        padded[: len(ids)] = ids
        runs = {p: g["embed"][jnp.asarray(padded)] for p in (("float32", control) if control else ("float32",))}
        for layer in range(cfg["num_hidden_layers"]):
            w = _layer_weights32(frozen, layer, seed_a, dtype_name)
            for precision in runs:
                runs[precision] = layer_forward(cfg, seed_a, layer, w, runs[precision], weight_dtype, precision)
            del w
        lo, n = len(prompt) - 1, len(served)
        tok = np.zeros(next(b for b in _BUCKETS if b >= n), np.int32)
        tok[:n] = served
        own, low = gaps_at(g, runs["float32"], runs[control] if control else runs["float32"],
                           jnp.asarray(lo, jnp.int32), jnp.asarray(tok), control)
        row = {"gaps": np.asarray(own)[:n]}
        if control:
            row["control_gaps"] = np.asarray(low)[:n]
        out.append(row)
    return out


# ------------------------------------------------------------------ the numbers compared
# The limits are data of the configuration (``bench.limits`` in bench/configs/dots3-note-serve-ep8.json).
# Chip readings (TPU v5 lite, PR 26, my chip runs; 36 requests, 5,193 served tokens a window, teacher-forced;
# PERF.md section 2 has the table with every seed):
#   served_token_gap_mean  the mean, over every token of every request the window finished, of the gap by
#                          which the served token's reference logit lies below the reference's best. Sound
#                          runs 0.051-0.061 (13 seeds); the int8 reference's first choices at the same
#                          positions 0.198-0.213 (3 seeds).
#                          Limit 0.115, about their geometric mean.
#   served_token_gap       the widest such gap. NOT a limit of this configuration: sound runs read 2.3-4.3,
#                          the int8 control 3.2-3.3. Two choices in the layers are discrete, the router's
#                          top-k and the indexer's top-k: a rounding flip there changes a hidden state by a
#                          step (one expert's term of eight; one cached position of index_topk, for all
#                          128 heads at once) and not by an epsilon, and with seeded weights the attention
#                          is peaked (scores of std 2), so one flipped position can carry weight. Up to
#                          2,048 tokens of context, where every position is kept, the program reads as the
#                          dense configuration does (mean 0.001-0.004, widest 0.02-0.07); past it the mean
#                          is ten times that. The router's scores are float32 on both sides; the indexer's
#                          are float32 sums of products of bfloat16 inputs in the program, float32 here.
