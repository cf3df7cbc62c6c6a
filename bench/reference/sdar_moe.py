"""Plain reference of the sdar_moe decoder and of its generation by diffusion
over blocks, as ISSUE 44 writes them down. 48 identical pre-norm layers,
``h <- h + Attn(RMSNorm(h))`` then ``h <- h + MoE(RMSNorm(h))``, a final RMSNorm
before an untied head:

- attention: ``q, k, v = x W_q, x W_k, x W_v`` (32 query / 4 KV heads of 128, no
  bias); q and k pass an RMSNorm over each head's 128 dims (one learned scale
  each, shared by the heads), then rotate-half RoPE over all 128 dims at
  ``rope_theta``; scores ``q k^T / sqrt(128)``, query head ``n`` reading KV head
  ``n // 8``; **position i sees position j iff j // B <= i // B** (causal over
  blocks of ``B = block_length`` counted from position 0, a position seeing its
  whole block: the mask as an explicit matrix over all keys); softmax; ``W_o``;
- experts: ``p = softmax(x W_g)`` in float32 over the router's full width, the
  ``num_experts_per_tok`` largest, weights ``p_e / sum of the chosen``
  (``norm_topk_prob``), no bias, no scaling factor, no shared expert; every
  expert a SwiGLU. The chip's share: ``num_experts`` experts from
  ``first_held_expert`` are held, routing and the normalising sum are over all
  ``num_experts_total``, only held experts' terms are added.

Generation (``generate``, the family's ``block_diffusion_generate``): the
prompt's whole blocks are context; the ``len mod B`` tokens left over open the
first generated block as fixed positions, every other position of a block starts
masked. A denoising pass feeds the block (tokens where known, the mask id
elsewhere) after the context and takes at every masked position the best token
and its confidence (its softmax probability); the mask id's logit is left out of
both (a departure from the published script, in program and reference alike:
with random weights it would otherwise be emitted). ``low_confidence_static``
unmasks the ``B / denoising_steps`` most confident masked positions (the earlier
first among equals); ``low_confidence_dynamic`` every masked position whose
confidence is over ``confidence_threshold``, and never fewer. When no position
is masked the block is committed and the next one opened. Without a cache a
commit pass computes nothing new (a block's K and V are recomputed from its
final tokens by every later forward), so here it is a record in ``passes`` only.

Straight ``jax.numpy`` in float32 under ``highest`` matmul precision: no kernel,
no cache, no batching, nothing imported from the program. One function computes
every forward (``run``): the rows of a sequence carry their position, their
block and which copy they belong to, and the mask is written out from those.
``served_gaps`` lays a clean copy of a served row (prompt and served tokens)
beside a noised copy of its generated blocks: a noised position of block b sees
the clean blocks before b and the noised block b itself, so that all blocks of a
sequence share a forward a denoising step. Attention runs a block of queries at
a time only so that 3k rows fit beside the resident engine.

Weights come from ``--seed``: a leaf depends on (seed, layer, leaf name), an
expert's on (seed, layer, the expert's number in the whole model), so the
shares of one seed are the parts of one model. ``program_params`` lays the same
numbers into the program's parameter tree (layers stacked on a leading axis).

Precisions (``precision=``): ``"float32"`` the reference proper; ``"int8"`` the
serving control: every projection's weight rounded to int8 per output channel
and its input per row (the router stays float32). The limits are data of the
configuration (``bench.limits`` in bench/configs/sdar-30b-a3b-serve-ep8.json)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_LEAVES = ("ln1", "ln2", "q_norm", "k_norm", "norm")  # float32, 1 + normal / 8
CLEAN, NOISED = 0, 1


# ------------------------------------------------------------------ sizes
def held(cfg):
    return cfg.get("first_held_expert", 0), cfg["num_experts"]


def router_width(cfg):
    return cfg.get("num_experts_total") or cfg["num_experts"]


def leaf_shapes(cfg):
    """{leaf: shape} of one layer, without its routed experts."""
    hidden, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"ln1": (hidden,), "ln2": (hidden,), "q": (hidden, q), "k": (hidden, kv), "v": (hidden, kv),
            "o": (q, hidden), "q_norm": (hd,), "k_norm": (hd,), "router": (hidden, router_width(cfg))}


def weight_bytes(cfg, itemsize=2):
    """Bytes of this chip's weights: every layer's attention, router and held experts, embedding and head."""
    per_layer = sum(int(np.prod(s)) for n, s in leaf_shapes(cfg).items() if n not in NORM_LEAVES)
    per_layer += cfg["num_experts"] * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (cfg["num_hidden_layers"] * per_layer + 2 * cfg["vocab_size"] * cfg["hidden_size"]) * itemsize


def kv_bytes_a_token(cfg, itemsize=2):
    """K and V of one position over every layer."""
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


# ------------------------------------------------------------------ weights
def _base_key(seed):
    return jax.random.key(seed % (2**31 - 1) if isinstance(seed, int) else seed)


def seed_array(seed):
    return jnp.asarray(seed % (2**31 - 1), jnp.uint32)


def layer_key(seed, layer):
    return jax.random.fold_in(_base_key(seed), layer + 1)


def _draw(cfg, key, name, shape, dtype):
    """float32: norm scales (1 + normal / 8); in the weights' dtype: every matrix (normal x initializer_range)."""
    draw = jax.random.normal(key, shape, jnp.float32)
    if name in NORM_LEAVES:
        return 1.0 + 0.125 * draw  # a power of two: the product is exact
    return (cfg["initializer_range"] * draw).astype(dtype)


def layer_weights(cfg, seed, layer, dtype):
    """One layer's weights but its routed experts; ``layer`` may be traced (the layers are alike)."""
    key, shapes = layer_key(seed, layer), leaf_shapes(cfg)
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


PROGRAM_LEAF = {  # reference leaf -> path under the program's ``model/layers``
    "ln1": ("input_layernorm", "scale"), "ln2": ("post_attention_layernorm", "scale"),
    "q": ("self_attn", "q_proj", "kernel"), "k": ("self_attn", "k_proj", "kernel"),
    "v": ("self_attn", "v_proj", "kernel"), "o": ("self_attn", "o_proj", "kernel"),
    "q_norm": ("self_attn", "q_norm", "scale"), "k_norm": ("self_attn", "k_norm", "scale"),
    "router": ("mlp", "gate", "kernel"),
}
EXPERT_LEAF = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}


def _put(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def program_params(cfg, seed, dtype):
    """The same numbers in the program's parameter tree: every layer's leaves stacked on a leading axis under
    ``model/layers``, the held experts on a second."""
    g = global_weights(cfg, seed, dtype)
    first, count = held(cfg)
    layers_i = jnp.arange(cfg["num_hidden_layers"], dtype=jnp.int32)
    layers = {}
    for name, value in jax.lax.map(lambda l: layer_weights(cfg, seed, l, dtype), layers_i).items():
        _put(layers, PROGRAM_LEAF[name], value)
    stacked = jax.lax.map(lambda l: jax.lax.map(lambda e: expert_weights(cfg, seed, l, e, dtype),
                                                first + jnp.arange(count, dtype=jnp.int32)), layers_i)
    for name, value in stacked.items():  # the program holds all three [width, hidden]: gate and up out x in
        _put(layers, ("mlp", "experts", EXPERT_LEAF[name]), value if name == "down" else value.swapaxes(-1, -2))
    return {"model": {"embed_tokens": {"embedding": g["embed"]}, "norm": {"scale": g["norm"]}, "layers": layers},
            "lm_head": {"kernel": g["head"]}}


# ------------------------------------------------------------------ layer mathematics
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


def rows_of(n, block, start=0):
    """The rows of one clean sequence of ``n`` positions from ``start``: what ``run`` needs to know of each row."""
    pos = start + np.arange(n, dtype=np.int32)
    return {"pos": pos, "blk": pos // block, "copy": np.full(n, CLEAN, np.int32), "live": np.ones(n, bool)}


def visible(rows):
    """[T, T] bool, row i sees row j: **the block mask written out**. Among the rows of one copy, j's block is
    not after i's (causal over blocks, a block seeing itself whole); a noised row sees the clean rows of the
    blocks before its own and the noised rows of its own block; a clean row sees no noised row; nobody sees a
    padding row."""
    bi, bj = rows["blk"][:, None], rows["blk"][None, :]
    ci, cj = rows["copy"][:, None], rows["copy"][None, :]
    clean_i, clean_j = ci == CLEAN, cj == CLEAN
    seen = (clean_i & clean_j & (bj <= bi)) | (~clean_i & clean_j & (bj < bi)) | (~clean_i & ~clean_j & (bj == bi))
    return seen & rows["live"][None, :]


def _blocks(t, most):
    """A block length that divides ``t``, at most ``most``."""
    b = min(t, most)
    while t % b:
        b -= 1
    return b


def attention(cfg, w, x, rows, precision="float32", q_block=128):
    """One layer's attention on the rows x [T, hidden] (normed input) of one sequence -> [T, hidden]."""
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    theta = float(cfg["rope_theta"])
    k = _rope(_rmsnorm(_matmul(x, w["k"], precision).reshape(t, kv, hd), w["k_norm"], eps), rows["pos"], theta)
    v = _matmul(x, w["v"], precision).reshape(t, kv, hd)
    seen_all = visible(rows)
    bq = _blocks(t, q_block)

    def block(s0):
        at = s0 + jnp.arange(bq)
        q = _rmsnorm(_matmul(x[at], w["q"], precision).reshape(bq, kv, heads // kv, hd), w["q_norm"], eps)
        q = _rope(q, rows["pos"][at], theta)
        s = jnp.einsum("tgrh,sgh->grts", q, k, precision="highest") * hd ** -0.5
        o = jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(jnp.where(seen_all[at][None, None], s, -1e30), axis=-1), v,
                       precision="highest")
        return _matmul(o.reshape(bq, heads * hd), w["o"], precision)

    return jax.lax.map(block, jnp.arange(0, t, bq)).reshape(t, -1)


def _swiglu(x, gate, up, down, precision):
    return _matmul(jax.nn.silu(_matmul(x, gate, precision)) * _matmul(x, up, precision), down, precision)


def route(cfg, w, x):
    """(chosen experts [T, k] over the router's full width, weights [T, k]); float32 whatever the precision."""
    p = jax.nn.softmax(jnp.matmul(x, w["router"].astype(jnp.float32), precision="highest"), axis=-1)
    chosen, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return idx, chosen / chosen.sum(-1, keepdims=True) if cfg.get("norm_topk_prob", True) else chosen


class _Frozen(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def held_experts(cfg, seed, layer, weight_dtype, experts=None):
    """The held experts' (or ``experts = (first, count)``) weights of ``layer``, stacked [count, ...]."""
    first, count = experts if experts is not None else held(cfg)
    return _held_experts(_Frozen(cfg), seed_array(seed) if isinstance(seed, int) else seed,
                         jnp.asarray(layer, jnp.int32), jnp.dtype(weight_dtype).name, first, count)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _held_experts(cfg, seed, layer, weight_dtype, first, count):
    return jax.lax.map(lambda e: expert_weights(cfg, seed, layer, e, jnp.dtype(weight_dtype)),
                       first + jnp.arange(count, dtype=jnp.int32))


def routed_part(cfg, seed, layer, idx, wts, x, weight_dtype, precision="float32", experts=None, drawn=None):
    """sum over the held experts (or ``experts = (first, count)``) of w_k E_k(x) for the tokens that chose them;
    x [T, hidden]. Each expert runs on its own tokens only: their count is read back, and rounded up to a bucket."""
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


@functools.partial(jax.jit, static_argnums=(0, 4))
def _attn_step(cfg, w, h, rows, precision):
    """The residual form, first half, for the sequences ``h`` [S, T, hidden] one after another (``rows``: [S, T]
    each): (h + attention(RMSNorm(h)), the experts' normed input, the routing [S, T, k] twice)."""
    def one(args):
        h, rows = args
        x = _rmsnorm(h, w["ln1"], cfg["rms_norm_eps"])
        h = h + attention(cfg, w, x, rows, precision)
        x = _rmsnorm(h, w["ln2"], cfg["rms_norm_eps"])
        return (h, x) + route(cfg, w, x)

    return jax.lax.map(one, (h, rows))


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _layer_weights(cfg, layer, seed, weight_dtype):
    return layer_weights(cfg, seed, layer, jnp.dtype(weight_dtype))


def layer_forward(cfg, seed, layer, w, h, rows, weight_dtype, precision="float32", drawn=None):
    """One decoder layer on the rows ``h`` [S, T, hidden] of S sequences (``rows``: [S, T] each), a sequence at a
    time through attention; the experts see every sequence's tokens at once (a token's experts read that token
    alone); ``w`` from ``layer_weights``."""
    h, x, idx, wts = _attn_step(_Frozen(cfg), w, h, rows, precision)
    idx = jnp.where(rows["live"][..., None], idx, -1)  # a padding row chooses nobody
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    y = routed_part(cfg, seed, layer, flat(idx), flat(wts), flat(x), weight_dtype, precision, drawn=drawn)
    return h + y.reshape(h.shape)


def head_logits(cfg, g, h, precision="float32"):
    """Final norm and head for the rows of ``h`` [N, hidden] -> [N, vocab] float32."""
    return _matmul(_rmsnorm(h, g["norm"], cfg["rms_norm_eps"]), g["head"], precision)


def run(cfg, seed, ids, rows, weight_dtype="float32", precision="float32", g=None):
    """Hidden states [T, hidden] before the final norm of the rows ``ids`` [T] of one sequence (``rows``: their
    position, block, copy and which are padding). ``g`` spares the draw of the embedding where one seed's weights
    serve many calls."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    if g is None:
        g = global_weights(cfg, seed, jnp.dtype(weight_dtype))
    rows = {k: jnp.asarray(v)[None] for k, v in rows.items()}
    h = g["embed"][jnp.asarray(ids)].astype(jnp.float32)[None]
    for layer in range(cfg["num_hidden_layers"]):
        w = _layer_weights(_Frozen(cfg), layer, seed_array(seed), jnp.dtype(weight_dtype).name)
        h = layer_forward(cfg, seed, layer, w, h, rows, weight_dtype, precision)
    return h[0]


def forward(cfg, seed, ids, weight_dtype="float32", precision="float32"):
    """Whole forward of one sequence of token ids under the block mask -> logits [T, vocab]."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    g = global_weights(cfg, seed, jnp.dtype(weight_dtype))
    h = run(cfg, seed, ids, rows_of(len(ids), cfg["block_length"]), weight_dtype, precision, g)
    return head_logits(cfg, g, h, precision)


# ------------------------------------------------------------------ generation
def choices(cfg, logits):
    """(best token [N], its confidence [N], logits with the mask id's left out [N, vocab]) of rows of logits."""
    lg = jnp.where(jnp.arange(logits.shape[-1]) == cfg["mask_token_id"], -jnp.inf, logits)
    return jnp.argmax(lg, -1), jnp.exp(jnp.max(lg, -1) - jax.nn.logsumexp(lg, -1)), lg


def to_unmask(cfg, conf, masked, rule=None):
    """Which masked positions of one block a denoising pass unmasks, from their confidences [B]."""
    conf, masked = np.asarray(conf, np.float32), np.asarray(masked, bool)
    b = len(masked)
    c = np.where(masked, conf, -1.0)
    order = sorted(range(b), key=lambda i: (-c[i], i))  # the most confident first, the earlier among equals
    take = np.zeros(b, bool)
    take[order[: b // cfg["denoising_steps"]]] = True
    if (rule or cfg["remasking"]) == "low_confidence_dynamic":
        take |= conf > np.float32(cfg["confidence_threshold"])
    return take & masked


def generate(cfg, seed, prompt, max_new_tokens, rule=None, weight_dtype="float32"):
    """``block_diffusion_generate`` on one prompt: (tokens emitted, ``passes``). A pass is a dict: ``kind``
    (``denoise`` / ``commit``), ``start`` (the block's first position), ``fed`` (the B ids fed, the mask id where
    masked), ``masked`` before the pass and, for a denoising pass, ``logits`` [B, vocab] at the block's positions."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    bk, mask_id = cfg["block_length"], cfg["mask_token_id"]
    g = global_weights(cfg, seed, jnp.dtype(weight_dtype))
    prompt = [int(t) for t in prompt]
    r = len(prompt) % bk
    context, block = prompt[: len(prompt) - r], prompt[len(prompt) - r:] + [0] * (bk - r)
    masked = np.arange(bk) >= r
    fixed, out, passes = r, [], []
    while len(out) < max_new_tokens:
        fed = [mask_id if m else t for t, m in zip(block, masked)]
        record = {"start": len(context), "fed": list(fed), "masked": masked.copy()}
        passes.append(record)
        if not masked.any():  # the commit pass: the block is handed on and the next one opened
            record["kind"] = "commit"
            out += block[fixed:]
            context, block, masked, fixed = context + block, [0] * bk, np.ones(bk, bool), 0
            continue
        ids = np.asarray(context + fed, np.int32)
        h = run(cfg, seed, ids, rows_of(len(ids), bk), weight_dtype, "float32", g)
        logits = head_logits(cfg, g, h[len(context):])
        x0, conf, _ = choices(cfg, logits)
        take = to_unmask(cfg, conf, masked, rule)
        block = [int(x0[i]) if take[i] else block[i] for i in range(bk)]
        masked = masked & ~take
        record.update(kind="denoise", logits=np.asarray(logits), unmasked=take)
    return out[:max_new_tokens], passes


# ------------------------------------------------------------------ serving check
_BUCKETS = (512, 1024, 1536, 2048, 3200)  # rows of a served sequence's two copies, padded
_NOISED = (128, 256, 512, 1152)  # rows of its noised copy, padded, where the head is applied
_ROWS_A_GROUP = 32768  # padded rows whose float32 hidden states stay on the device at once, in every precision: 0.27 GB each


def _replay_rows(cfg, prompt, served):
    """The two copies of one served row: (ids of the clean copy, rows, where the noised copy starts, the
    generated blocks as (first position, fixed leading positions, served tokens of the block))."""
    bk = cfg["block_length"]
    r = len(prompt) % bk
    base = len(prompt) - r
    clean = list(prompt) + list(served)
    clean += [0] * (-len(clean) % bk)  # the last block's positions past the served tokens: seen by nobody below
    blocks, at = [], 0
    for start in range(base, len(clean), bk):
        fixed = r if start == base else 0
        blocks.append((start, fixed, list(served[at: at + bk - fixed])))
        at += bk - fixed
    n_clean, n_noised = len(clean), len(blocks) * bk
    total = next((b for b in _BUCKETS if b >= n_clean + n_noised), n_clean + n_noised)
    pos = np.zeros(total, np.int32)
    pos[:n_clean] = np.arange(n_clean)
    pos[n_clean:n_clean + n_noised] = base + np.arange(n_noised)
    copy = np.zeros(total, np.int32)
    copy[n_clean:] = NOISED
    live = np.arange(total) < n_clean + n_noised
    return clean, {"pos": pos, "blk": pos // bk, "copy": copy, "live": live}, n_clean, blocks


@functools.partial(jax.jit, static_argnums=(0, 6))
def _noised_choices(cfg, g, h, h_low, at, served, control):
    """At the noised rows ``at`` [N] of one sequence's hidden states: the reference's best token and its confidence,
    the gap by which the token ``served`` [N] there lies under the best, and the same gap of the token the control
    precision puts first (from ``h_low``, the same rows computed in that precision)."""
    x0, conf, lg = choices(cfg, head_logits(cfg, g, h[at], "float32"))
    best = lg.max(-1)
    gap = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    if control is None:
        return x0, conf, gap, gap
    low = choices(cfg, head_logits(cfg, g, h_low[at], control))[0]
    return x0, conf, gap, best - jnp.take_along_axis(lg, low[:, None], -1)[:, 0]


def _replay_step(cfg, g, seed_a, group, weight_dtype, control):
    """One denoising step of every block of every sequence of ``group`` (sequences padded to one number of rows)
    that still has a masked position: one forward of every sequence's two copies, a layer's weights drawn once for
    the group. False: nothing was masked."""
    bk, mask_id = cfg["block_length"], cfg["mask_token_id"]
    frozen, dtype_name = _Frozen(cfg), jnp.dtype(weight_dtype).name
    precisions = ("float32", control) if control else ("float32",)
    if not any(m.any() for rp in group if rp for _, m in rp["state"]):
        return False
    ids = np.zeros((len(group), len(group[0]["ids"])), np.int32)
    for s, rp in enumerate(group):
        for j, (tok, msk) in enumerate(rp["state"] if rp else ()):
            lo = rp["n_clean"] + j * bk
            rp["ids"][lo: lo + bk] = np.where(msk, mask_id, tok)
        if rp:
            ids[s] = rp["ids"]
    rows = {k: jnp.asarray(np.stack([rp["rows"][k] if rp else np.zeros_like(group[0]["rows"][k]) for rp in group]))
            for k in group[0]["rows"]}
    first = g["embed"][jnp.asarray(ids)].astype(jnp.float32)
    h = {p: first for p in precisions}
    for layer in range(cfg["num_hidden_layers"]):
        w = _layer_weights(frozen, layer, seed_a, dtype_name)
        drawn = held_experts(cfg, seed_a, layer, weight_dtype)
        for p in precisions:
            h[p] = layer_forward(cfg, seed_a, layer, w, h[p], rows, weight_dtype, p, drawn)
        del w, drawn
    for s, rp in enumerate(group):
        if not rp:
            continue
        n = len(rp["blocks"]) * bk
        padded = next((b for b in _NOISED if b >= n), n)
        at = np.minimum(rp["n_clean"] + np.arange(padded), len(rp["ids"]) - 1)
        served = np.zeros(padded, np.int32)
        for j, (_, fixed, toks) in enumerate(rp["blocks"]):
            served[j * bk + fixed: j * bk + fixed + len(toks)] = toks
        x0, conf, gap, low_gap = (np.asarray(a) for a in _noised_choices(
            frozen, g, h["float32"][s], h[control][s] if control else h["float32"][s], jnp.asarray(at),
            jnp.asarray(served), control))
        for j, (tok, msk) in enumerate(rp["state"]):
            _, fixed, toks = rp["blocks"][j]
            for i in np.flatnonzero(to_unmask(cfg, conf[j * bk: (j + 1) * bk], msk, "low_confidence_static")):
                if i - fixed < len(toks):
                    tok[i] = toks[i - fixed]
                    rp["gaps"][j][i - fixed] = float(gap[j * bk + i])
                    rp["control"][j][i - fixed] = float(low_gap[j * bk + i])
                else:  # max_tokens cut the served block short here: the reference's own choice, and no gap
                    tok[i] = x0[j * bk + i]
                msk[i] = False
    return True


def served_gaps(cfg, seed, sequences, weight_dtype, control=None):
    """The serving comparison: ``sequences`` is a list of (prompt ids, served ids). Each served block is replayed:
    its context is teacher-forced from the served tokens (the clean copy); at every denoising step the reference
    takes its own most confident masked position of the block, records by how much the served token's logit there
    lies under its best, and fills that position with the *served* token (its own choice where the request's
    ``max_tokens`` cut the served block short: such a position has no gap). All blocks of a sequence share a
    forward a step; sequences padded to one number of rows pass a layer together, one after another through its
    attention. Returned per sequence: ``gaps`` [served tokens] and, with ``control``, ``control_gaps``: the gap
    under the reference of the token that precision puts first at the same positions of the same inputs."""
    cfg = {k: v for k, v in cfg.items() if k != "bench"}
    bk = cfg["block_length"]
    seed_a = seed_array(seed)
    g = jax.jit(lambda s: global_weights(cfg, s, jnp.dtype(weight_dtype)))(seed_a)
    replays = []
    for prompt, served in sequences:
        clean, rows, n_clean, blocks = _replay_rows(cfg, prompt, served)
        ids = np.zeros(len(rows["pos"]), np.int32)
        ids[:n_clean] = clean
        state = []  # a block's tokens and which of its positions are masked
        for start, fixed, _ in blocks:
            tok = np.zeros(bk, np.int32)
            tok[:fixed] = clean[start: start + fixed]
            state.append([tok, np.arange(bk) >= fixed])
        replays.append({"ids": ids, "rows": rows, "n_clean": n_clean, "blocks": blocks, "state": state,
                        "gaps": [[None] * len(t) for _, _, t in blocks],
                        "control": [[None] * len(t) for _, _, t in blocks]})
    by_rows = {}
    for rp in replays:
        by_rows.setdefault(len(rp["ids"]), []).append(rp)
    for total, rps in sorted(by_rows.items()):
        size = max(1, _ROWS_A_GROUP // (total * (2 if control else 1)))  # sequences a group: one shape a bucket
        for lo in range(0, len(rps), size):
            group = rps[lo: lo + size]
            group += [None] * (size - len(group))  # dead sequences: every row padding
            for _ in range(bk):
                if not _replay_step(cfg, g, seed_a, group, weight_dtype, control):
                    break
    out = []
    for rp in replays:
        res = {"gaps": np.asarray([x for blk in rp["gaps"] for x in blk], np.float32)}
        if control:
            res["control_gaps"] = np.asarray([x for blk in rp["control"] for x in blk], np.float32)
        out.append(res)
    return out
