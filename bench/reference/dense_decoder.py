"""Plain reference of a dense pre-norm decoder (Qwen2 / Llama shape): RMSNorm,
GQA attention with rotate-half RoPE and optional q/k/v biases, SwiGLU MLP, tied
or untied head. Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no batching,
nothing imported from the program. Weights come from ``--seed`` through
``layer_weights`` / ``global_weights`` below; the harness builds the program's
parameter tree from the same functions (``program_params``), so both sides
hold the same numbers without the reference ever reading the program's.

Precisions (``precision=``):

- ``"float32"``  the reference proper.
- ``"int8"``     a serving control: every matmul's weight is rounded to int8
                 per output channel and its input to int8 per row (absmax), the
                 step below the bfloat16 the serving configurations state.
- ``"bfloat16"`` the training control: parameters, gradients, Adam moments and
                 every matmul in bfloat16, the step below float32 parameters.

The numbers compared and the chip readings their limits were set from are at
the bottom of this file; the limits themselves are data of each configuration
(``bench.limits`` in its file), since a configuration states its precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1", "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w", "ln2", "gate_w", "up_w", "down_w")
GLOBAL_LEAVES = ("embed", "norm")


# ------------------------------------------------------------------ weights
def _dims(cfg):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return cfg["hidden_size"], heads, kv, cfg["hidden_size"] // heads, cfg["intermediate_size"]


def layer_weights(cfg, key, dtype):
    """One layer's weights from its key; float32 draws rounded to ``dtype``.
    Norm scales stay float32 whatever ``dtype`` is, as the program keeps them."""
    hidden, heads, kv, hd, ffn = _dims(cfg)
    shapes = {"q_w": (hidden, heads * hd), "q_b": (heads * hd,), "k_w": (hidden, kv * hd),
              "k_b": (kv * hd,), "v_w": (hidden, kv * hd), "v_b": (kv * hd,),
              "o_w": (heads * hd, hidden), "gate_w": (hidden, ffn), "up_w": (hidden, ffn),
              "down_w": (ffn, hidden), "ln1": (hidden,), "ln2": (hidden,)}
    out = {}
    for i, name in enumerate(LAYER_LEAVES):
        draw = jax.random.normal(jax.random.fold_in(key, i), shapes[name], jnp.float32)
        # 0.125 is a power of two: the product is exact, so fused or not the sum rounds once
        out[name] = 1.0 + 0.125 * draw if name.startswith("ln") else (cfg["initializer_range"] * draw).astype(dtype)
    return out


def _base_key(seed):
    """``seed`` is a Python int (any size) or a traced uint32. Inside ``jit`` pass it
    as an argument, never as a constant: XLA would fold the whole draw at compile
    time, and every seed would be another program."""
    return jax.random.key(seed % (2**31 - 1) if isinstance(seed, int) else seed)


def seed_array(seed):
    return jnp.asarray(seed % (2**31 - 1), jnp.uint32)


def layer_key(seed, layer):
    return jax.random.fold_in(_base_key(seed), layer + 1)


def global_weights(cfg, seed, dtype):
    key = jax.random.fold_in(_base_key(seed), 0)
    embed = cfg["initializer_range"] * jax.random.normal(jax.random.fold_in(key, 0),
                                         (cfg["vocab_size"], cfg["hidden_size"]), jnp.float32)
    norm = 1.0 + 0.125 * jax.random.normal(jax.random.fold_in(key, 1), (cfg["hidden_size"],), jnp.float32)
    return {"embed": embed.astype(dtype), "norm": norm}


def stacked_weights(cfg, seed, dtype):
    """All layers stacked on a leading axis, plus the globals: one traceable call."""
    keys = jax.vmap(lambda l: layer_key(seed, l))(jnp.arange(cfg["num_hidden_layers"]))
    layers = jax.vmap(lambda k: layer_weights(cfg, k, dtype))(keys)
    return {"layers": layers, **global_weights(cfg, seed, dtype)}


def program_params(cfg, seed, dtype):
    """The same numbers in the program's parameter tree (scanned flax Llama
    module: ``model/layers/<module>/kernel`` with a leading layer axis)."""
    if not cfg.get("tie_word_embeddings", True):
        raise NotImplementedError("untied head: add an lm_head leaf here and in the reference")
    w = stacked_weights(cfg, seed, dtype)
    lw = w["layers"]
    proj = lambda n: {"kernel": lw[n + "_w"], "bias": lw[n + "_b"]}
    return {"model": {
        "embed_tokens": {"embedding": w["embed"]},
        "norm": {"scale": w["norm"]},
        "layers": {
            "input_layernorm": {"scale": lw["ln1"]},
            "post_attention_layernorm": {"scale": lw["ln2"]},
            "self_attn": {"q_proj": proj("q"), "k_proj": proj("k"), "v_proj": proj("v"),
                          "o_proj": {"kernel": lw["o_w"]}},
            "mlp": {"gate_proj": {"kernel": lw["gate_w"]}, "up_proj": {"kernel": lw["up_w"]},
                    "down_proj": {"kernel": lw["down_w"]}},
        }}}


PROGRAM_LEAF = {  # reference leaf name -> path in the program's tree
    "embed": ("model", "embed_tokens", "embedding"), "norm": ("model", "norm", "scale"),
    "ln1": ("model", "layers", "input_layernorm", "scale"),
    "ln2": ("model", "layers", "post_attention_layernorm", "scale"),
    "q_w": ("model", "layers", "self_attn", "q_proj", "kernel"),
    "q_b": ("model", "layers", "self_attn", "q_proj", "bias"),
    "k_w": ("model", "layers", "self_attn", "k_proj", "kernel"),
    "k_b": ("model", "layers", "self_attn", "k_proj", "bias"),
    "v_w": ("model", "layers", "self_attn", "v_proj", "kernel"),
    "v_b": ("model", "layers", "self_attn", "v_proj", "bias"),
    "o_w": ("model", "layers", "self_attn", "o_proj", "kernel"),
    "gate_w": ("model", "layers", "mlp", "gate_proj", "kernel"),
    "up_w": ("model", "layers", "mlp", "up_proj", "kernel"),
    "down_w": ("model", "layers", "mlp", "down_proj", "kernel"),
}


def program_leaves(tree):
    """{reference leaf name: array} out of a tree shaped like ``program_params``."""
    out = {}
    for name, path in PROGRAM_LEAF.items():
        node = tree
        for p in path:
            node = node[p]
        out[name] = node
    return out


# ------------------------------------------------------------------ forward
def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _matmul(x, w, precision):
    if precision == "int8":
        return jnp.matmul(_fake_int8(x, -1), _fake_int8(w, 0), precision="highest")
    if precision == "bfloat16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [T, n, hd], positions 0..T-1, rotate-half convention."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., hd // 2:], x32[..., : hd // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def layer_forward(cfg, w, h, precision="float32"):
    """One decoder layer on one sequence ``h`` [T, hidden], causal."""
    _, heads, kv, hd, _ = _dims(cfg)
    t = h.shape[0]
    mm = functools.partial(_matmul, precision=precision)
    x = _rmsnorm(h, w["ln1"], cfg["rms_norm_eps"])
    q = (mm(x, w["q_w"]) + w["q_b"]).reshape(t, heads, hd)
    k = (mm(x, w["k_w"]) + w["k_b"]).reshape(t, kv, hd)
    v = (mm(x, w["v_w"]) + w["v_b"]).reshape(t, kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    s = jnp.einsum("tnh,snh->nts", q.astype(jnp.float32), k.astype(jnp.float32),
                   precision="highest") * hd ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    a = jnp.einsum("nts,snh->tnh", p, v.astype(jnp.float32), precision="highest")
    h = h + mm(a.reshape(t, heads * hd).astype(h.dtype), w["o_w"])
    x = _rmsnorm(h, w["ln2"], cfg["rms_norm_eps"])
    gate, up = mm(x, w["gate_w"]), mm(x, w["up_w"])
    return h + mm(jax.nn.silu(gate) * up, w["down_w"])


def head_logits(cfg, g, h, precision="float32"):
    """Final norm and tied head for the rows of ``h`` [N, hidden] -> [N, vocab] float32."""
    x = _rmsnorm(h, g["norm"], cfg["rms_norm_eps"])
    return _matmul(x, g["embed"].T, precision).astype(jnp.float32)


# ------------------------------------------------------------------ serving check
_BUCKETS = (64, 256, 512, 1024, 1536, 2048, 2560, 3072, 4096)


def served_gaps(cfg, seed, sequences, weight_dtype, control=None):
    """The serving comparison. ``sequences`` is a list of (prompt ids, served
    ids). Runs the reference once over each prompt with its served tokens
    (teacher-forced, layer by layer so one float32 layer is resident at a
    time) and returns, per sequence, the gap by which each served token's
    logit lies below the reference's best. With ``control`` (a precision name)
    it also returns the gap, under the reference, of the token that precision
    puts first at each of the same positions."""
    weight_dtype = jnp.dtype(weight_dtype)
    hs, spans = [], []
    g = jax.jit(lambda s: global_weights(cfg, s, weight_dtype))(seed_array(seed))
    g = {k: v.astype(jnp.float32) for k, v in g.items()}
    for prompt, served in sequences:
        ids = np.asarray(list(prompt) + list(served[:-1]), np.int32)
        width = next(b for b in _BUCKETS if b >= len(ids))
        padded = np.zeros(width, np.int32)
        padded[: len(ids)] = ids
        hs.append(g["embed"][jnp.asarray(padded)])
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(served)))
    runs = {"float32": list(hs)}
    if control:
        runs[control] = list(hs)
    step = jax.jit(layer_forward, static_argnums=(0, 3))
    frozen = _Frozen(cfg)
    one_layer = jax.jit(lambda s, l: {k: v.astype(jnp.float32) for k, v in
                                      layer_weights(cfg, layer_key(s, l), weight_dtype).items()})
    for layer in range(cfg["num_hidden_layers"]):
        w = one_layer(seed_array(seed), jnp.asarray(layer, jnp.int32))
        for precision, states in runs.items():
            for i, h in enumerate(states):
                states[i] = step(frozen, w, h, precision)
    def gaps_at(g, h_ref, h_low, lo, tok, control):
        """For the positions lo, lo+1, ... (as many as ``tok`` has rows; rows past the
        served tokens are padding): the reference's best logit less its logit of the
        served token, and less its logit of the control's first choice."""
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
    for i, ((prompt, served), (lo, hi)) in enumerate(zip(sequences, spans)):
        n = hi - lo
        tok = np.zeros(next(b for b in _BUCKETS if b >= n), np.int32)
        tok[:n] = served
        own, low = gaps_at(g, runs["float32"][i], runs[control][i] if control else runs["float32"][i],
                           jnp.asarray(lo, jnp.int32), jnp.asarray(tok), control)
        row = {"gaps": np.asarray(own)[:n]}
        if control:
            row["control_gaps"] = np.asarray(low)[:n]
        out.append(row)
    return out


class _Frozen(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


# ------------------------------------------------------------------ training check
def _loss_sum(cfg, params, row, precision):
    """Summed next-token cross entropy of one row [T] (float32)."""
    h = params["embed"][row]

    def body(h, w):
        return jax.checkpoint(lambda h, w: layer_forward(cfg, w, h, precision))(h, w), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    logits = head_logits(cfg, params, h[:-1], precision)
    picked = jnp.take_along_axis(logits, row[1:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def _leafwise(tree):
    return {**{k: tree["layers"][k] for k in LAYER_LEAVES}, **{k: tree[k] for k in GLOBAL_LEAVES}}


def _sq_norms(tree):
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32))) for k, v in _leafwise(tree).items()}


def train_trajectory(cfg, seed, batches, optim, precision="float32"):
    """The training comparison: the first ``len(batches)`` AdamW steps of the
    configuration, row by row. Returns per step the loss, and per leaf the norm
    of the first gradient as the optimizer gets it (after the global-norm clip)
    and of the parameters' change over all the steps."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    init = jax.jit(lambda s: jax.tree.map(lambda x: x.astype(dtype), stacked_weights(cfg, s, dtype)))
    params = init(seed_array(seed))
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    mu, nu = zeros(), zeros()
    b1, b2, eps, lr = optim["adam_beta1"], optim["adam_beta2"], optim["adam_epsilon"], optim["learning_rate"]
    decay = {"layers": {k: (optim["weight_decay"] if k.endswith("_w") else 0.0) for k in LAYER_LEAVES},
             "embed": optim["weight_decay"], "norm": 0.0}

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


# ------------------------------------------------------------------ the numbers compared
# The limits are data of each configuration (``bench.limits`` in its file). What each
# number is, and the chip readings (TPU v5 lite, PR 23, my chip runs) the limits of the
# two Qwen2 configurations were set from; PERF.md section 2 has the same table.
#
# serving (qwen2-1.5b-serve, bf16; every token of every request the window finished, about
# 3,500 served tokens of 31 requests, teacher-forced; controls: the reference with int8 weights
# and inputs, and the program with its own int8 KV pool, ``precision.control_engine``)
#   served_token_gap       widest gap by which a served token's reference logit lies below the
#                          reference's best. Sound runs: largest 0.114 over the 3 seeds read before
#                          the limit was set (0.089 0.111 0.114; later ones: PERF.md section 2). Control:
#                          the gap of the int8 reference's first choice at the same positions,
#                          smallest 0.613 over 3 seeds (0.613 0.661 0.747). Limit 0.25, about their
#                          geometric mean. An altered token reads about 1 or more (a random token
#                          lies far below); a widest gap swings by its nature, so the mean stands by it.
#   served_token_gap_mean  the mean of the same gaps: steady from seed to seed. Sound runs: largest
#                          0.0021 over the same 3 seeds (0.0033 over 39 earlier samples of 300-700
#                          tokens); control smallest 0.0384 over 3 seeds (0.0434 0.0416 0.0384).
#                          Limit 0.006.
#   The program's own int8 KV pool does not build at the cell's size: its scale arrays
#   f32[28,2,9600,2,16,1] pad 64 times in HBM (RESOURCE_EXHAUSTED, 19.38 of 15.75 GiB, 3 seeds):
#   a control that crashes has failed, and sets no upper end (PERF.md section 7).
# training (qwen2-0.5b-pretrain, fp32 parameters, bf16 compute; control: bf16 parameters)
#   loss_gap          largest |loss - reference| / reference over the three steps. Sound:
#                     largest 1.77e-4 over 22 seeds (one seed; the others 1.01e-4 at most); control
#                     smallest 9.8e-4 over 5. It is there to catch rows left out of a batch (a
#                     quarter of the batch moves the loss by some 1e-3 at seeded weights). Limit
#                     4.5e-4: 2.5 times the sound largest, 2.2 times under the control.
#   first_grad_gap    worst_leaf_gap of the first gradient's norms as AdamW gets them (from its
#                     first moment after one step). Sound: largest 5.2e-3 over 22 seeds; the
#                     control reads 1.9e-3..8.6e-3, no higher than sound runs, so this number is
#                     held against a wrong or missing gradient, not the precision. Limit 1.4e-2.
#   param_delta_gap   worst_leaf_gap of the parameters' change after the three steps. Sound:
#                     largest 1.16e-3 over 22 seeds; control smallest 0.0890 over 5 (a norm scale
#                     near 1 cannot take a 3e-4 step in bfloat16). A step that returns its state
#                     unchanged reads 1. Limit 0.01, nine times the one and a ninth of the other.
