"""Plain reference of the nemotron_h hybrid decoder, as ISSUE 33 writes its
blocks down: every block is one mixer under a pre-norm residual, ``x <- x +
mixer(RMSNorm(x))``, picked by its character of ``hybrid_override_pattern``:

- ``M``  Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC <- SiLU(causal_conv1d(xBC) + b)``;
  ``x`` [H, P], ``B`` and ``C`` [G, N], head ``h`` reads group ``h // (H / G)``;
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``; ``y_t = h_t C_t + D x_t``;
  ``y <- RMSNorm_groups(y * SiLU(z))``; out ``y W_out``. **The recurrence runs
  token by token** (a ``lax.scan`` over positions): no chunked form, no cache.
- ``E``  ``s = sigmoid(u W_g)`` in float32 over the router's full width, top-k of
  ``s + bias``, weights ``s`` of the chosen, normalised, times
  ``routed_scaling_factor``; expert ``relu(u W_up)^2 W_down``; plus one shared
  expert of the same form on every token. The chip's share: ``n_routed_experts``
  experts from ``first_held_expert`` are held, only their terms are added.
- ``*``  ``q, k, v = u W_q, u W_k, u W_v``, causal softmax of ``q k^T / sqrt(d)``,
  ``W_o``; grouped KV heads; **no rotary embedding** (the configuration file's
  first ``assumed`` entry says why).

Straight ``jax.numpy`` in float32 under ``highest`` matmul precision, nothing
imported from the program. Sequences of like length are padded together (a
causal model's padding changes nothing before it) and attention runs a block
of queries at a time, only so that the check fits beside the engine's 12.7 GB;
each expert runs on the tokens that chose it.

Weights come from ``--seed``: a leaf depends on (seed, block, leaf name), an
expert's on (seed, block, the expert's number in the whole model), so the
shares of one seed are the parts of one model. ``program_params`` lays the
same numbers into the program's parameter tree. What is drawn: bottom of this
file, with the limits' readings.

Precisions (``precision=``): ``"float32"`` the reference proper; ``"int8"`` the
serving control: every projection's weight rounded to int8 per output channel
and its input per row (the router, the convolution and the recurrence stay
float32)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SCAN, EXPERTS, ATTENTION = "M", "E", "*"
BIAS_STD = 0.05  # of the seeded e_score_correction_bias
CONV_STD = 0.3  # of the seeded convolution taps and bias (torch's default for 4 taps is U(-0.5, 0.5): std 0.29)
DT_RANGE, A_RANGE = (1e-3, 1e-1), (1.0, 16.0)  # softplus(dt_bias) log-uniform in the first, -A uniform in the second


# ------------------------------------------------------------------ sizes
def dims(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"heads": heads, "p": p, "d_in": heads * p, "g": g, "n": n, "k": cfg["conv_kernel"],
            "conv_dim": heads * p + 2 * g * n}


def held(cfg):
    return cfg.get("first_held_expert", 0), cfg["n_routed_experts"]


def router_width(cfg):
    return cfg.get("n_routed_experts_total") or cfg["n_routed_experts"]


def kind_of(cfg, layer):
    return cfg["hybrid_override_pattern"][layer]


def leaf_shapes(cfg, layer):
    """{leaf: shape} of one block, without its routed experts."""
    hidden, kind = cfg["hidden_size"], kind_of(cfg, layer)
    out = {"norm": (hidden,)}
    if kind == SCAN:
        d = dims(cfg)
        out.update({"in_proj": (hidden, d["d_in"] + d["conv_dim"] + d["heads"]), "conv_w": (d["k"], d["conv_dim"]),
                    "conv_b": (d["conv_dim"],), "A_log": (d["heads"],), "D": (d["heads"],), "dt_bias": (d["heads"],),
                    "gate_norm": (d["d_in"],), "out_proj": (d["d_in"], hidden)})
    elif kind == EXPERTS:
        width = cfg["moe_shared_expert_intermediate_size"]
        out.update({"router": (hidden, router_width(cfg)), "router_bias": (router_width(cfg),),
                    "sh_up": (hidden, width), "sh_down": (width, hidden)})
    elif kind == ATTENTION:
        q, kv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
        out.update({"q": (hidden, q), "k": (hidden, kv), "v": (hidden, kv), "o": (q, hidden)})
    else:
        raise ValueError(f"no block kind {kind!r}")
    return out


# ------------------------------------------------------------------ weights
def _base_key(seed):
    return jax.random.key(seed % (2**31 - 1) if isinstance(seed, int) else seed)


def seed_array(seed):
    return jnp.asarray(seed % (2**31 - 1), jnp.uint32)


def layer_key(seed, layer):
    return jax.random.fold_in(_base_key(seed), layer + 1)


def _draw(cfg, key, name, shape, dtype):
    """float32: norm scales and D (1 + normal / 8), the router's bias, the conv bias, A_log, dt_bias;
    in the weights' dtype: the conv taps (normal x 0.3) and every matrix (normal x initializer_range)."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    if name == "dt_bias":  # softplus^-1 of a log-uniform step size
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
        return dt + jnp.log(-jnp.expm1(-dt))
    draw = jax.random.normal(key, shape, jnp.float32)
    if name in ("norm", "gate_norm", "D"):
        return 1.0 + 0.125 * draw  # a power of two: the product is exact
    if name == "router_bias":
        return BIAS_STD * draw
    if name == "conv_b":
        return CONV_STD * draw
    if name == "conv_w":
        return (CONV_STD * draw).astype(dtype)
    return (cfg["initializer_range"] * draw).astype(dtype)


def layer_weights(cfg, seed, layer, dtype):
    """One block's weights but its routed experts; ``layer`` is a Python int."""
    key, shapes = layer_key(seed, layer), leaf_shapes(cfg, layer)
    return {n: _draw(cfg, jax.random.fold_in(key, i), n, shapes[n], dtype) for i, n in enumerate(sorted(shapes))}


def expert_weights(cfg, seed, layer, expert, dtype):
    """Routed expert ``expert`` (its number in the whole model; may be traced) of block ``layer``."""
    key = jax.random.fold_in(jax.random.fold_in(layer_key(seed, layer), 1000), expert)
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    shapes = {"up": (hidden, width), "down": (width, hidden)}
    return {n: _draw(cfg, jax.random.fold_in(key, i), "expert", s, dtype) for i, (n, s) in enumerate(shapes.items())}


def global_weights(cfg, seed, dtype):
    key = jax.random.fold_in(_base_key(seed), 0)
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return {"embed": _draw(cfg, jax.random.fold_in(key, 0), "embed", shape, dtype),
            "norm": _draw(cfg, jax.random.fold_in(key, 1), "norm", (cfg["hidden_size"],), dtype),
            "head": _draw(cfg, jax.random.fold_in(key, 2), "head", shape[::-1], dtype)}


PROGRAM_LEAF = {  # reference leaf -> path under the program's ``model/layers_<i>``
    "norm": ("norm", "scale"), "conv_w": ("mixer", "conv1d", "kernel"),
    "conv_b": ("mixer", "conv1d", "bias"), "A_log": ("mixer", "A_log"), "D": ("mixer", "D"),
    "dt_bias": ("mixer", "dt_bias"), "gate_norm": ("mixer", "norm", "scale"),
    "out_proj": ("mixer", "out_proj", "kernel"), "router": ("mixer", "gate", "kernel"),
    "router_bias": ("mixer", "e_score_correction_bias"), "sh_up": ("mixer", "shared_experts", "up_proj", "kernel"),
    "sh_down": ("mixer", "shared_experts", "down_proj", "kernel"), "q": ("mixer", "q_proj", "kernel"),
    "k": ("mixer", "k_proj", "kernel"), "v": ("mixer", "v_proj", "kernel"), "o": ("mixer", "o_proj", "kernel"),
}
EXPERT_LEAF = {"up": "up_proj", "down": "down_proj"}


def _put(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def program_params(cfg, seed, dtype):
    """The same numbers in the program's parameter tree (unrolled
    ``model/layers_<i>``, the held experts stacked on a leading axis). The
    program keeps ``in_proj`` as its three column blocks and an expert's
    ``up_proj`` out x in: the same numbers, cut and turned."""
    g = global_weights(cfg, seed, dtype)
    model = {"embed_tokens": {"embedding": g["embed"]}, "norm": {"scale": g["norm"]}}
    first, count = held(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        tree = model.setdefault(f"layers_{layer}", {})
        for name, value in layer_weights(cfg, seed, layer, dtype).items():
            if name == "in_proj":
                d = dims(cfg)
                for part, lo, hi in (("z", 0, d["d_in"]), ("xbc", d["d_in"], d["d_in"] + d["conv_dim"]),
                                     ("dt", d["d_in"] + d["conv_dim"], value.shape[1])):
                    _put(tree, ("mixer", "in_proj", part, "kernel"), value[:, lo:hi])
            else:
                _put(tree, PROGRAM_LEAF[name], value)
        if kind_of(cfg, layer) == EXPERTS:
            stacked = jax.lax.map(lambda e: expert_weights(cfg, seed, layer, e, dtype),
                                  first + jnp.arange(count, dtype=jnp.int32))
            for name, value in stacked.items():
                _put(tree, ("mixer", "experts", EXPERT_LEAF[name]), jnp.swapaxes(value, 1, 2) if name == "up" else value)
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


def scan_mixer(cfg, w, u, precision="float32"):
    """The Mamba-2 mixer on whole sequences u [B, T, hidden] from zero state, the recurrence one position at a time."""
    d, eps = dims(cfg), cfg["norm_eps"]
    b, t, _ = u.shape
    heads, p, g, n, k = d["heads"], d["p"], d["g"], d["n"], d["k"]
    zxbcdt = _matmul(u, w["in_proj"], precision)
    z, xbc, dt = zxbcdt[..., : d["d_in"]], zxbcdt[..., d["d_in"]: d["d_in"] + d["conv_dim"]], zxbcdt[..., -heads:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(padded[:, j: j + t] * w["conv_w"][j].astype(jnp.float32) for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[..., : d["d_in"]].reshape(b, t, heads, p)
    group_of_head = jnp.arange(heads) // (heads // g)
    b_g = xbc[..., d["d_in"]: d["d_in"] + g * n].reshape(b, t, g, n)
    c_g = xbc[..., d["d_in"] + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [B, T, H]
    a = -jnp.exp(w["A_log"])

    def step(h, xs):  # h [B, H, P, N]; B_t and C_t [B, G, N], read by head through its group
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = b_t[:, group_of_head], c_t[:, group_of_head]
        h = jnp.exp(dt_t * a)[..., None, None] * h + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    per_token = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), jnp.float32),
                        (per_token(x), per_token(dt), per_token(b_g), per_token(c_g)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x
    gated = (y.reshape(b, t, -1) * jax.nn.silu(z)).reshape(b, t, g, -1)
    normed = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return _matmul(normed.reshape(b, t, -1) * w["gate_norm"], w["out_proj"], precision)


def attention(cfg, w, u, precision="float32", q_block=256):
    """Causal softmax attention on whole sequences u [B, T, hidden], no position embedding; a sequence and a block of
    queries at a time."""
    b, t, _ = u.shape
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _matmul(u, w["q"], precision).reshape(b, t, kv, heads // kv, hd)
    k = _matmul(u, w["k"], precision).reshape(b, t, kv, hd)
    v = _matmul(u, w["v"], precision).reshape(b, t, kv, hd)
    bq = min(t, q_block)
    while t % bq:
        bq -= 1
    pos = jnp.arange(t)

    def one(qkv):
        q1, k1, v1 = qkv

        def block(s0):
            rows = s0 + jnp.arange(bq)
            s = jnp.einsum("tgrh,sgh->grts", q1[rows], k1, precision="highest") * hd ** -0.5
            s = jnp.where((pos[None, :] <= rows[:, None])[None, None], s, -1e30)
            return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(s, axis=-1), v1, precision="highest")

        return jax.lax.map(block, jnp.arange(0, t, bq)).reshape(t, heads * hd)

    return _matmul(jax.lax.map(one, (q, k, v)), w["o"], precision)


def _relu2(x, up, down, precision):
    return _matmul(jnp.square(jax.nn.relu(_matmul(x, up, precision))), down, precision)


def route(cfg, w, x):
    """(chosen experts [N, k] over the router's full width, weights [N, k]); float32 whatever the precision."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"].astype(jnp.float32), precision="highest"))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, chosen / chosen.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision="float32", experts=None):
    """sum over the held experts (or ``experts = (first, count)``) of w_k E_k(x)
    for the tokens that chose them; x [N, hidden]. Each expert runs on its own
    tokens only: their count is read back, and rounded up to a bucket."""
    first, count = experts if experts is not None else held(cfg)
    n = x.shape[0]
    sizes = np.asarray(jnp.sum(idx[:, :, None] == (first + jnp.arange(count))[None, None, :], axis=(0, 1)))
    cap = max(64, n // 16)
    while cap < sizes.max(initial=0):
        cap *= 2
    return _routed_part(_Frozen(cfg), seed_array(seed) if isinstance(seed, int) else seed,
                        jnp.asarray(layer, jnp.int32), idx, wts, x, jnp.dtype(weight_dtype).name, precision,
                        first, count, min(cap, n))


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9, 10))
def _routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision, first, count, cap):
    n = x.shape[0]

    def one(out, e):
        w = expert_weights(cfg, seed, layer, e, jnp.dtype(weight_dtype))
        mine = idx == e  # [N, k]
        weight = jnp.sum(jnp.where(mine, wts, 0.0), -1)
        rows = jnp.nonzero(mine.any(-1), size=cap, fill_value=n)[0]
        xs = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], 0)[rows]
        y = _relu2(xs, w["up"], w["down"], precision)
        scale = jnp.concatenate([weight, jnp.zeros((1,), weight.dtype)], 0)[rows]
        return out.at[rows].add(y * scale[:, None], mode="drop"), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), first + jnp.arange(count, dtype=jnp.int32))
    return out


class _Frozen(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _layer_weights32(cfg, layer, seed, weight_dtype):
    return {k: v.astype(jnp.float32) for k, v in layer_weights(cfg, seed, layer, jnp.dtype(weight_dtype)).items()}


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _mixer(cfg, kind, w, h, precision):
    """(normed input, what the block adds but for its routed experts) of whole sequences h [B, T, hidden]."""
    u = _rmsnorm(h, w["norm"], cfg["norm_eps"])
    if kind == SCAN:
        return u, scan_mixer(cfg, w, u, precision)
    if kind == ATTENTION:
        return u, attention(cfg, w, u, precision)
    return u, _relu2(u, w["sh_up"], w["sh_down"], precision)


_route = jax.jit(route, static_argnums=(0,))


def layer_forward(cfg, seed, layer, w, h, weight_dtype, precision="float32", real=None):
    """One block on whole sequences ``h`` [B, T, hidden], causal; ``w`` from ``layer_weights`` in float32;
    ``real`` [B, T] bool leaves padding out of the routing counts (it routes nowhere)."""
    cfg = _Frozen(cfg)
    kind = kind_of(cfg, layer)
    u, y = _mixer(cfg, kind, w, h, precision)
    if kind == EXPERTS:
        flat = u.reshape(-1, u.shape[-1])
        idx, wts = _route(cfg, w, flat)
        if real is not None:
            idx = jnp.where(real.reshape(-1, 1), idx, -1)
        y = y + routed_part(cfg, seed, layer, idx, wts, flat, weight_dtype, precision).reshape(u.shape)
    return h + y


def head_logits(cfg, g, h, precision="float32"):
    """Final norm and head for the rows of ``h`` [N, hidden] -> [N, vocab] float32."""
    return _matmul(_rmsnorm(h, g["norm"], cfg["norm_eps"]), g["head"], precision)


def forward(cfg, seed, ids, weight_dtype="float32", precision="float32"):
    """Whole forward of one sequence of token ids -> logits [T, vocab] (tests and small sizes)."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    g = {k: v.astype(jnp.float32) for k, v in global_weights(cfg, seed, jnp.dtype(weight_dtype)).items()}
    h = g["embed"][jnp.asarray(ids)][None]
    for layer in range(cfg["num_hidden_layers"]):
        w = _layer_weights32(_Frozen(cfg), layer, seed_array(seed), jnp.dtype(weight_dtype).name)
        h = layer_forward(cfg, seed, layer, w, h, weight_dtype, precision)
    return head_logits(cfg, g, h[0], precision)


# ------------------------------------------------------------------ serving check
_BUCKETS = (64, 128, 256, 512, 1024, 1536, 2048, 2560)
_SERVED_BUCKETS = (64, 128, 256, 512)
_TOKENS_A_BATCH = 8192  # padded positions a batch: the float32 activations of one block stay under a GB


def served_gaps(cfg, seed, sequences, weight_dtype, control=None):
    """The serving comparison, as ``dense_decoder.served_gaps``: ``sequences`` is a
    list of (prompt ids, served ids); each runs through the reference once,
    teacher-forced, sequences of like length padded together, one block at a
    time; returned per sequence is the gap by which each served token's logit
    lies below the reference's best and, with ``control``, the gap under the
    reference of the token that precision puts first at the same positions."""
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
    fed = [np.asarray(list(p) + list(s[:-1]), np.int32) for p, s in sequences]
    bucket_of = [next((b for b in _BUCKETS if b >= len(ids)), len(ids)) for ids in fed]
    out = [None] * len(sequences)
    for bucket in sorted(set(bucket_of)):
        members = [i for i, b in enumerate(bucket_of) if b == bucket]
        rows = max(1, _TOKENS_A_BATCH // bucket)
        for at in range(0, len(members), rows):
            batch = members[at: at + rows]
            ids = np.zeros((rows, bucket), np.int32)
            real = np.zeros((rows, bucket), bool)
            for r, i in enumerate(batch):
                ids[r, : len(fed[i])], real[r, : len(fed[i])] = fed[i], True
            real = jnp.asarray(real)
            runs = {p: g["embed"][jnp.asarray(ids)] for p in (("float32", control) if control else ("float32",))}
            for layer in range(cfg["num_hidden_layers"]):
                w = _layer_weights32(frozen, layer, seed_a, dtype_name)
                for precision in runs:
                    runs[precision] = layer_forward(cfg, seed_a, layer, w, runs[precision], weight_dtype, precision,
                                                    real)
                del w
            for r, i in enumerate(batch):
                prompt, served = sequences[i]
                n = len(served)
                tok = np.zeros(next((b for b in _SERVED_BUCKETS if b >= n), n), np.int32)
                tok[:n] = served
                own, low = gaps_at(g, runs["float32"][r], runs[control][r] if control else runs["float32"][r],
                                   jnp.asarray(len(prompt) - 1, jnp.int32), jnp.asarray(tok), control)
                out[i] = {"gaps": np.asarray(own)[:n]}
                if control:
                    out[i]["control_gaps"] = np.asarray(low)[:n]
    return out


# ------------------------------------------------------------------ what is drawn, and the numbers compared
# Seeded weights (``_draw``): every matrix normal x initializer_range (0.02) in the weights' dtype; norm scales,
# the gated norm's scale and D 1 + normal / 8; the router's selection bias normal x 0.05; the convolution's four
# taps and its bias normal x 0.3 (torch's default for four taps is U(-0.5, 0.5), std 0.29); A = -U(1, 16) a head
# (A_log its logarithm), dt_bias the inverse softplus of a step size drawn log-uniform in 0.001 .. 0.1, as Mamba-2
# initialises them (the configuration's time_step_min / max). On a normed input the in-projection adds a normal of
# std about 1 to dt_bias, so softplus gives dt in about 0.0004 .. 0.3 and exp(dt A) in 0.008 .. 0.9996 a step:
# some heads forget in a few tokens, others keep a trace of a whole 2,560-token sequence.
# The limits are data of the configuration (``bench.limits`` in bench/configs/nemotron3-nano-serve-ep8.json);
# the chip readings behind them are in that file's ``limits_note`` and in PERF.md section 2.
