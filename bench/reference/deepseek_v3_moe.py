"""Plain reference of a ``deepseek_v3`` decoder as kanana-2-30b-a3b sets it, for
training: latent attention without a q latent (32 heads, query/key head 128 + 64,
value head 128, one roped key head shared by all), a dense SwiGLU first layer,
then expert layers (sigmoid router, top-k of score + bias, the chosen scores
normalised and scaled, SwiGLU experts, one shared SwiGLU), pre-norm RMSNorm,
untied embedding and head. Written from the published layer equations in
straight ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``:
no kernel, no remat policy of the program's, nothing imported from the program.

The configuration states a share: ``n_routed_experts`` experts are held, from
``first_held_expert`` on, of ``n_routed_experts_total`` the router scores. The
reference is given the same share: the router runs over all of them, what an
absent expert would add to a token is left out, and that partial result goes on
to the next layer. (A departure from the published model, as the program's: a
whole model holds every expert.) With every expert held it is the model.

Weights come from ``--seed`` through ``layer_weights`` / ``global_weights``; the
harness builds the program's parameter tree from the same draws
(``program_params``), so both sides hold the same numbers without the reference
ever reading the program's.

Precisions (``precision=``): ``"float32"`` the reference proper; ``"bfloat16"``
the training control: parameters, gradients, Adam moments and every product in
bfloat16, the step below the float32 parameters the configuration states (the
router's scores stay float32 arithmetic on the rounded weights).

The numbers compared are ``dense_decoder.py``'s three (``loss_gap``,
``first_grad_gap``, ``param_delta_gap``); the limits are data of the
configuration (``bench.limits``), the chip readings they were set from are in
PERF.md section 2."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN_LEAVES = ("ln1", "q_w", "kva_w", "kv_ln", "kvb_w", "o_w", "ln2")
DENSE_LEAVES = ATTN_LEAVES + ("gate_w", "up_w", "down_w")
MOE_LEAVES = ATTN_LEAVES + ("router_w", "router_b", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
GLOBAL_LEAVES = ("embed", "norm", "head")
NORMS = ("ln1", "ln2", "kv_ln", "norm")
QUERY_BLOCK = 1024  # attention is computed in blocks of queries: a row's scores at 8,192 are 8.6 GB in float32


# ------------------------------------------------------------------ sizes
def _dims(cfg):
    return dict(hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"], lora=cfg["kv_lora_rank"],
                ffn=cfg["intermediate_size"], width=cfg["moe_intermediate_size"],
                held=cfg["n_routed_experts"], total=cfg.get("n_routed_experts_total") or cfg["n_routed_experts"],
                first=cfg.get("first_held_expert", 0), shared=cfg["n_shared_experts"], k=cfg["num_experts_per_tok"])


def _is_moe(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def _shapes(cfg, moe):
    d = _dims(cfg)
    hidden, heads = d["hidden"], d["heads"]
    out = {"ln1": (hidden,), "q_w": (hidden, heads * (d["nope"] + d["rope"])), "kva_w": (hidden, d["lora"] + d["rope"]),
           "kv_ln": (d["lora"],), "kvb_w": (d["lora"], heads * (d["nope"] + d["v"])), "o_w": (heads * d["v"], hidden),
           "ln2": (hidden,)}
    if not moe:
        return {**out, "gate_w": (hidden, d["ffn"]), "up_w": (hidden, d["ffn"]), "down_w": (d["ffn"], hidden)}
    wide = d["width"] * d["shared"]
    return {**out, "router_w": (hidden, d["total"]), "router_b": (d["total"],),
            "e_gate": (d["held"], hidden, d["width"]), "e_up": (d["held"], hidden, d["width"]),
            "e_down": (d["held"], d["width"], hidden),
            "s_gate": (hidden, wide), "s_up": (hidden, wide), "s_down": (wide, hidden)}


# ------------------------------------------------------------------ weights
def layer_weights(cfg, key, dtype, moe):
    """One layer's weights from its key; float32 draws rounded to ``dtype``. Norm
    scales and the router's selection bias stay float32, as the program keeps them.
    The bias is drawn (std 0.005) and not zero, so that the choice of experts is the
    choice of score + bias and not of the score: at this size it changes an expert of
    about a quarter of the tokens, which a program that left it out would show, and
    leaves the seeded router's load near even, which is what a trained bias is for (at
    std 0.05 one chip's 16 experts of 128 receive 9-16% of the assignments from seed to
    seed where 12.5% is even, and a step's work moves with the seed)."""
    out = {}
    for i, (name, shape) in enumerate(_shapes(cfg, moe).items()):
        draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in NORMS:
            out[name] = 1.0 + 0.125 * draw  # 0.125 is a power of two: the product is exact
        elif name == "router_b":
            out[name] = 0.005 * draw
        else:
            out[name] = (cfg["initializer_range"] * draw).astype(dtype)
    return out


def _base_key(seed):
    """``seed`` is a Python int (any size) or a traced uint32. Inside ``jit`` pass it
    as an argument, never as a constant."""
    return jax.random.key(seed % (2**31 - 1) if isinstance(seed, int) else seed)


def seed_array(seed):
    return jnp.asarray(seed % (2**31 - 1), jnp.uint32)


def layer_key(seed, layer):
    return jax.random.fold_in(_base_key(seed), layer + 1)


def global_weights(cfg, seed, dtype):
    key = jax.random.fold_in(_base_key(seed), 0)
    vocab, hidden, std = cfg["vocab_size"], cfg["hidden_size"], cfg["initializer_range"]
    embed = std * jax.random.normal(jax.random.fold_in(key, 0), (vocab, hidden), jnp.float32)
    norm = 1.0 + 0.125 * jax.random.normal(jax.random.fold_in(key, 1), (hidden,), jnp.float32)
    head = std * jax.random.normal(jax.random.fold_in(key, 2), (hidden, vocab), jnp.float32)
    return {"embed": embed.astype(dtype), "norm": norm, "head": head.astype(dtype)}


def all_weights(cfg, seed, dtype):
    """{"layers": [one dict a layer], **globals}: one traceable call."""
    layers = [layer_weights(cfg, layer_key(seed, l), dtype, _is_moe(cfg, l)) for l in range(cfg["num_hidden_layers"])]
    return {"layers": layers, **global_weights(cfg, seed, dtype)}


def program_params(cfg, seed, dtype):
    """The same numbers in the program's parameter tree (unrolled flax modules:
    ``model/layers_<i>/<module>/kernel``; the held experts stacked under ``mlp/experts``)."""
    w = all_weights(cfg, seed, dtype)
    model = {"embed_tokens": {"embedding": w["embed"]}, "norm": {"scale": w["norm"]}}
    for i, lw in enumerate(w["layers"]):
        k = lambda n: {"kernel": lw[n]}
        attn = {"q_proj": k("q_w"), "kv_a_proj_with_mqa": k("kva_w"), "kv_a_layernorm": {"scale": lw["kv_ln"]},
                "kv_b_proj": k("kvb_w"), "o_proj": k("o_w")}
        if _is_moe(cfg, i):
            mlp = {"gate": {"kernel": lw["router_w"], "e_score_correction_bias": lw["router_b"]},
                   "experts": {"gate_proj": lw["e_gate"], "up_proj": lw["e_up"], "down_proj": lw["e_down"]},
                   "shared_experts": {"gate_proj": k("s_gate"), "up_proj": k("s_up"), "down_proj": k("s_down")}}
        else:
            mlp = {"gate_proj": k("gate_w"), "up_proj": k("up_w"), "down_proj": k("down_w")}
        model[f"layers_{i}"] = {"input_layernorm": {"scale": lw["ln1"]},
                                "post_attention_layernorm": {"scale": lw["ln2"]}, "self_attn": attn, "mlp": mlp}
    return {"model": model, "lm_head": {"kernel": w["head"]}}


def _layer_paths(moe):
    attn = {"ln1": ("input_layernorm", "scale"), "ln2": ("post_attention_layernorm", "scale"),
            "q_w": ("self_attn", "q_proj", "kernel"), "kva_w": ("self_attn", "kv_a_proj_with_mqa", "kernel"),
            "kv_ln": ("self_attn", "kv_a_layernorm", "scale"), "kvb_w": ("self_attn", "kv_b_proj", "kernel"),
            "o_w": ("self_attn", "o_proj", "kernel")}
    if not moe:
        return {**attn, **{n + "_w": ("mlp", n + "_proj", "kernel") for n in ("gate", "up", "down")}}
    return {**attn, "router_w": ("mlp", "gate", "kernel"), "router_b": ("mlp", "gate", "e_score_correction_bias"),
            **{"e_" + n: ("mlp", "experts", n + "_proj") for n in ("gate", "up", "down")},
            **{"s_" + n: ("mlp", "shared_experts", n + "_proj", "kernel") for n in ("gate", "up", "down")}}


def program_leaves(tree):
    """{reference leaf name: the program's array}: a layer's leaves are ``L<i>.<name>``."""
    def at(node, path):
        for p in path:
            node = node[p]
        return node

    out = {"embed": tree["model"]["embed_tokens"]["embedding"], "norm": tree["model"]["norm"]["scale"],
           "head": tree["lm_head"]["kernel"]}
    i = 0
    while f"layers_{i}" in tree["model"]:
        layer = tree["model"][f"layers_{i}"]
        for name, path in _layer_paths("experts" in layer["mlp"]).items():
            out[f"L{i}.{name}"] = at(layer, path)
        i += 1
    return out


def _leafwise(params):
    """The reference's own tree under the same leaf names."""
    out = {k: params[k] for k in GLOBAL_LEAVES}
    for i, lw in enumerate(params["layers"]):
        out.update({f"L{i}.{k}": v for k, v in lw.items()})
    return out


# ------------------------------------------------------------------ forward
def _matmul(x, w, precision):
    if precision == "float32":
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32), precision="highest")
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _rope_interleaved(x, theta):
    """x [T, heads, d] with the published pair layout (x0, x1), (x2, x3), ...
    (``rope_interleave``): pairs are brought to the half layout (x0, x2, ..., x1, x3, ...)
    and rotated there, at positions 0..T-1; float32."""
    t, d = x.shape[0], x.shape[-1]
    x32 = x.astype(jnp.float32)
    x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], -1)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def attention(cfg, w, x, precision):
    """x [T, hidden] normed -> [T, hidden]: q = x W_q split (nope | pe) a head; kv_a = x W_kva,
    c = rmsnorm(kv_a[:lora]), k_pe = kv_a[lora:] (one head); [k_nope | v] = c W_kvb a head;
    RoPE on q_pe and k_pe; scores q.k (nope + rope)^-1/2, causal softmax, o = P v, o W_o."""
    d = _dims(cfg)
    t, heads, nope, rope, dv = x.shape[0], d["heads"], d["nope"], d["rope"], d["v"]
    q = _matmul(x, w["q_w"], precision).reshape(t, heads, nope + rope)
    kv_a = _matmul(x, w["kva_w"], precision)
    c = _rmsnorm(kv_a[:, : d["lora"]], w["kv_ln"], cfg["rms_norm_eps"])
    kvb = _matmul(c, w["kvb_w"], precision).reshape(t, heads, nope + dv)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_pe = _rope_interleaved(q[..., nope:], cfg["rope_theta"])
    k_pe = _rope_interleaved(kv_a[:, None, d["lora"]:], cfg["rope_theta"])  # [T, 1, rope]
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, heads, rope))], -1)
    scale = (nope + rope) ** -0.5
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"a row of {t} positions is not a whole number of query blocks of {block}")

    @jax.checkpoint  # a block's probabilities are made again in the backward, never kept for every block
    def one_block(q_blk, start):
        if precision == "float32":
            s = jnp.einsum("qhd,khd->hqk", q_blk, k, precision="highest") * scale
        else:
            s = jnp.einsum("qhd,khd->hqk", q_blk, k, preferred_element_type=jnp.float32) * scale
        rows = start + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if precision == "float32":
            return jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
        return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v)

    starts = jnp.arange(0, t, block)
    o = jax.lax.map(lambda a: one_block(*a), (q.reshape(t // block, block, heads, nope + rope), starts))
    return _matmul(o.reshape(t, heads * dv), w["o_w"], precision)


def _swiglu(x, gate, up, down, precision):
    return _matmul(jax.nn.silu(_matmul(x, gate, precision)) * _matmul(x, up, precision), down, precision)


def route(cfg, w, x):
    """x [T, hidden] -> (experts [T, k] over the router's whole width, weights [T, k]):
    s = sigmoid(x W_g) in float32; the top k of s + b (one group: ``n_group`` 1, so no
    group step); weights s_i / sum of the chosen s, times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), w["router_w"].astype(jnp.float32), precision="highest"))
    _, idx = jax.lax.top_k(s + w["router_b"].astype(jnp.float32), cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True) * cfg["routed_scaling_factor"]


def experts(cfg, w, x, precision):
    """y = sum over the chosen experts that are held of w_i E_i(x), + S(x). An expert is run
    over every token and weighted by zero where it was not chosen: plain, and the same sum."""
    d = _dims(cfg)
    idx, weights = route(cfg, w, x)

    def add_expert(y, e):
        gate, up, down, number = e
        share = jnp.sum(jnp.where(idx == number, weights, 0.0), -1)  # [T] float32; 0 where not chosen
        out = jax.checkpoint(_swiglu, static_argnums=(4,))(x, gate, up, down, precision)
        return y + share[:, None] * out.astype(jnp.float32), None

    numbers = d["first"] + jnp.arange(d["held"])
    y, _ = jax.lax.scan(add_expert, jnp.zeros(x.shape, jnp.float32), (w["e_gate"], w["e_up"], w["e_down"], numbers))
    return y.astype(x.dtype) + _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], precision)


def layer_forward(cfg, w, h, precision="float32"):
    """One pre-norm layer on h [T, hidden]; an expert layer where ``w`` holds a router."""
    h = h + attention(cfg, w, _rmsnorm(h, w["ln1"], cfg["rms_norm_eps"]), precision)
    x = _rmsnorm(h, w["ln2"], cfg["rms_norm_eps"])
    if "router_w" in w:
        return h + experts(cfg, w, x, precision)
    return h + _swiglu(x, w["gate_w"], w["up_w"], w["down_w"], precision)


def forward(cfg, params, row, precision="float32"):
    """Logits [T, vocabulary slice] (float32) of one row of token ids [T]."""
    h = params["embed"][row]
    for w in params["layers"]:
        h = jax.checkpoint(lambda h, w: layer_forward(cfg, w, h, precision))(h, w)
    return _matmul(_rmsnorm(h, params["norm"], cfg["rms_norm_eps"]), params["head"], precision).astype(jnp.float32)


# ------------------------------------------------------------------ training check
def _loss_sum(cfg, params, row, precision):
    """Summed next-token cross entropy of one row [T] over the vocabulary slice (float32)."""
    logits = forward(cfg, params, row, precision)[:-1]
    picked = jnp.take_along_axis(logits, row[1:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def _sq_norms(tree):
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32))) for k, v in _leafwise(tree).items()}


def _decays(name):
    return not (name in NORMS or name == "router_b")


def train_trajectory(cfg, seed, batches, optim, precision=None):
    """The training comparison: the first ``len(batches)`` AdamW steps of the
    configuration, row by row. Returns per step the loss, and per leaf the norm
    of the first gradient as the optimizer gets it (after the global-norm clip)
    and of the parameters' change over all the steps. The router's bias has no
    gradient and no decay: both its norms are zero."""
    precision = precision or "float32"
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    init = jax.jit(lambda s: jax.tree.map(lambda x: x.astype(dtype), all_weights(cfg, s, dtype)))
    params = init(seed_array(seed))
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    mu, nu = zeros(), zeros()
    b1, b2, eps, lr = optim["adam_beta1"], optim["adam_beta2"], optim["adam_epsilon"], optim["learning_rate"]
    decay = {"layers": [{k: (optim["weight_decay"] if _decays(k) else 0.0) for k in lw} for lw in params["layers"]],
             **{k: (optim["weight_decay"] if _decays(k) else 0.0) for k in GLOBAL_LEAVES}}

    @functools.partial(jax.jit, donate_argnums=(1,))
    def add_row_grad(params, acc, loss, row, scale):
        ls, g = jax.value_and_grad(lambda p: _loss_sum(cfg, p, row, precision) * scale)(params)
        return jax.tree.map(jnp.add, acc, g), loss + ls

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def apply(params, grads, mu, nu, t):
        norm = jnp.sqrt(sum(_sq_norms(grads).values()))
        clip = optim["max_grad_norm"]
        grads = jax.tree.map(lambda g: jnp.where(norm < clip, g, (g / norm.astype(g.dtype)) * clip), grads)
        first = jax.tree.map(jnp.sqrt, _sq_norms(grads))
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        tf = t.astype(jnp.float32)

        def upd(p, m, v, wd):
            u = (m / (1 - b1 ** tf)).astype(p.dtype) / (jnp.sqrt((v / (1 - b2 ** tf)).astype(p.dtype)) + eps)
            return (p - lr * (u + wd * p)).astype(p.dtype)

        return jax.tree.map(upd, params, mu, nu, decay), mu, nu, first

    losses, first_grad = [], None
    for t, batch in enumerate(batches, 1):
        batch = np.asarray(batch, np.int32)
        scale = 1.0 / (batch.shape[0] * (batch.shape[1] - 1))
        acc, loss = zeros(), jnp.zeros((), jnp.float32)
        for row in batch:
            acc, loss = add_row_grad(params, acc, loss, jnp.asarray(row), scale)
        losses.append(float(loss))
        params, mu, nu, first = apply(params, acc, mu, nu, jnp.asarray(t))
        if t == 1:
            first_grad = {k: float(v) for k, v in first.items()}
    del mu, nu
    delta = jax.jit(lambda p, p0: jax.tree.map(
        jnp.sqrt, _sq_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))))(
        params, init(seed_array(seed)))
    return {"losses": losses, "first_grad_norm": first_grad,
            "param_delta_norm": {k: float(v) for k, v in delta.items()}}


def worst_leaf_gap(got, want):
    """The largest, over leaves, gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    floor = float(np.median(list(want.values())))
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in want)
