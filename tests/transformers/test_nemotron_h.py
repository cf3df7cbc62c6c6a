"""The state-space layer kind's mathematics (``transformers/state_layers.py``),
the expert layer told its body (``transformers/latent_layers.py``) and the
nemotron_h whole-sequence module, at small sizes on the CPU, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.transformers import NemotronHConfig, NemotronHForCausalLM
from paddlenlp_tpu.transformers import latent_layers as L
from paddlenlp_tpu.transformers import state_layers as S
from tests.experimental.test_state_serving import SMALL  # the small preset the engine tests serve

SEED = 5


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "nemotron_h")


def scan_inputs(lengths=(13, 9), t=13, g=2, r=2, p=4, n=8):
    b = len(lengths)
    k = jax.random.split(jax.random.key(0), 6)
    x, bm, cm = (jax.random.normal(k[0], (b, t, g, r, p)), jax.random.normal(k[1], (b, t, g, n)),
                 jax.random.normal(k[2], (b, t, g, n)))
    valid = jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]
    dt = jnp.where(valid[..., None, None], jax.nn.softplus(jax.random.normal(k[3], (b, t, g, r)) - 2), 0.0)
    a = -jnp.exp(jax.random.normal(k[4], (g, r)))
    return x, dt, a, bm, cm, jax.random.normal(k[5], (b, g, r, p, n)), valid


@pytest.mark.parametrize("chunk", [4, 8, 13])
def test_the_chunk_form_is_the_token_by_token_recurrence_with_a_padded_tail(chunk):
    """13 positions in sub-chunks of 4 (a tail of 1, padded to 4), of 8 and of 13; the second row has 9 real
    tokens and 4 of padding (dt = 0): its outputs up to 9 and its state after 9 are those of the scan over 9."""
    x, dt, a, bm, cm, h0, valid = scan_inputs()
    y_scan, h_scan = S.ssm_scan(x, dt, a, bm, cm, h0)
    y, h = S.ssd_chunk(x, dt, a, bm, cm, h0, chunk)
    assert np.abs(y - y_scan)[np.asarray(valid)].max() < 1e-5 and np.abs(h - h_scan).max() < 2e-6
    _, h9 = S.ssm_scan(x[1:, :9], dt[1:, :9], a, bm[1:, :9], cm[1:, :9], h0[1:])
    assert np.abs(h[1:] - h9).max() < 2e-6  # the padded tail left the state untouched


def test_one_step_continues_a_chunk():
    x, dt, a, bm, cm, h0, _ = scan_inputs(lengths=(13, 13))
    y_all, h_all = S.ssm_scan(x, dt, a, bm, cm, h0)
    _, h12 = S.ssd_chunk(x[:, :12], dt[:, :12], a, bm[:, :12], cm[:, :12], h0, 4)
    y, h = S.ssm_step(x[:, 12:], dt[:, 12:], a, bm[:, 12:], cm[:, 12:], h12)
    assert np.abs(y[:, 0] - y_all[:, 12]).max() < 1e-5 and np.abs(h - h_all).max() < 2e-6


def test_the_module_is_the_reference(ref):
    m = NemotronHForCausalLM(NemotronHConfig(**SMALL))
    m.params = jax.jit(lambda s: ref.program_params(SMALL, s, jnp.float32))(ref.seed_array(SEED))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), m.param_shapes)
    assert want == jax.tree.map(lambda a: (a.shape, a.dtype), m.params)  # the reference lays out the program's tree
    ids = np.random.RandomState(1).randint(0, 97, (2, 19))
    got = np.asarray(m(jnp.asarray(ids))[0] if isinstance(m(jnp.asarray(ids)), tuple) else m(jnp.asarray(ids)))
    for row in range(2):
        assert np.abs(got[row] - np.asarray(ref.forward(SMALL, SEED, ids[row]))).max() < 5e-5


def test_the_eight_shares_of_an_expert_block_sum_to_the_uncut_layer(ref):
    """A block of 16 routed experts and one shared: the program's layer on the shares (first, 2) for first = 0,
    2, .. 14 gives eight partial results; their routed parts and the shared expert counted once add up to the
    reference's uncut layer (all 16 held)."""
    whole = dict(SMALL, n_routed_experts=16, n_routed_experts_total=16, first_held_expert=0)
    layer = 1  # an E block
    w = {k: jnp.asarray(v, jnp.float32) for k, v in ref.layer_weights(whole, SEED, layer, jnp.float32).items()}
    u = jax.random.normal(jax.random.key(2), (40, SMALL["hidden_size"]), jnp.float32)
    idx, wts = ref.route(whole, w, u)
    uncut = ref.routed_part(whole, SEED, layer, idx, wts, u, "float32") + ref._relu2(u, w["sh_up"], w["sh_down"], "float32")
    shared = {"up_proj": {"kernel": w["sh_up"]}, "down_proj": {"kernel": w["sh_down"]}}
    total = L.RELU2.dense(shared, u)
    for first in range(0, 16, 2):
        cfg = NemotronHConfig(**dict(SMALL, n_routed_experts=2, n_routed_experts_total=16, first_held_expert=first))
        share = dict(SMALL, n_routed_experts=2, n_routed_experts_total=16, first_held_expert=first)
        p = {"gate": {"kernel": w["router"]}, "e_score_correction_bias": w["router_bias"], "shared_experts": shared,
             "experts": ref.program_params(share, SEED, jnp.float32)["model"][f"layers_{layer}"]["mixer"]["experts"]}
        y, chosen = L.moe(p, u, cfg, body=L.RELU2)
        assert np.array_equal(np.asarray(chosen), np.asarray(idx))  # every share routes over all 16 alike
        total = total + (y - L.RELU2.dense(shared, u))
    assert np.abs(total - uncut).max() < 2e-5


def test_the_swiglu_body_is_still_dots3s(ref):
    """``experts_held`` takes the expert's body from the caller since PR 33: with the SwiGLU body (the default) it
    is PR 26's layer, held against the dots3 reference's routed part on dots3's small preset."""
    from paddlenlp_tpu.transformers import Dots3NoteConfig
    from tests.experimental.test_latent_serving import SMALL as DOTS3

    dots = loader.module_from("reference", "dots3_note")
    cfg, layer = Dots3NoteConfig(**DOTS3), 2
    experts = dots.program_params(DOTS3, 3, jnp.float32)["model"][f"layers_{layer}"]["mlp"]["experts"]
    w = {k: jnp.asarray(v, jnp.float32) for k, v in dots.layer_weights(DOTS3, 3, layer, jnp.float32).items()}
    x = jax.random.normal(jax.random.key(4), (33, DOTS3["hidden_size"]), jnp.float32)
    idx, wts = dots.route(DOTS3, w, x)
    want = dots.routed_part(DOTS3, 3, layer, idx, wts, x, "float32")
    first, count = cfg.experts_held
    for body in ({}, {"body": L.SWIGLU}):
        got = L.experts_held(experts, x, idx.astype(jnp.int32), wts, first, count, **body)
        assert np.abs(got - want).max() < 1e-6
    assert L.experts_held.__defaults__[-1] is L.SWIGLU and L.moe.__defaults__[-1] is L.SWIGLU


def test_the_configuration_yields_its_kinds_and_refuses_by_name():
    cfg = NemotronHConfig()
    kinds = cfg.layer_kinds()
    assert kinds[:7] == ["ssm", "experts", "ssm", "experts", "ssm", "attention", "experts"] and len(kinds) == 52
    assert cfg.rotary_attention is False and cfg.rms_norm_eps == 1e-5 and cfg.experts_held == (0, 128)
    assert cfg.inference_model.endswith("state_model.StateSpaceInferenceModel")
    for bad, named in (({"hybrid_override_pattern": "M-" * 26}, "dense MLP block"), ({"mlp_hidden_act": "silu"}, "relu"),
                       ({"n_group": 2}, "group-limited"), ({"attention_bias": True}, "without bias"),
                       ({"sliding_window": 128}, "sliding_window"), ({"num_hidden_layers": 50}, "52 blocks"),
                       ({"n_routed_experts": 16, "n_routed_experts_total": 128, "first_held_expert": 120}, "lie outside")):
        with pytest.raises(ValueError, match=named):
            NemotronHConfig(**bad)
