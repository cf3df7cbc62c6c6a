"""FLOPs and bytes against hand-worked values for Qwen2-0.5B at 4 x 2048."""

import json
import os

import pytest

from bench.harness import loader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "qwen2-0.5b-pretrain.json")))


def test_dense_decoder_flops_by_hand():
    k = loader.module_from("kernels", "dense_decoder_flops")
    # a layer: q 896*896, k and v 896*128 each, o 896*896, mlp 3*896*4864
    layer = 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864
    assert layer == 14_909_440
    assert k.matmul_params(CFG) == 24 * layer + 151936 * 896 == 493_961_216
    # forward a token: 2 a parameter + 2*T*hidden a layer of causal attention
    assert k.forward_flops_per_token(CFG, 2048) == 2 * 493_961_216 + 2 * 2048 * 896 * 24
    assert k.train_flops_per_token(CFG, 2048) == 3 * 1_076_002_816 == 3_228_008_448


@pytest.mark.parametrize("kernel,matmuls,nbytes", [
    # q and o are 4*2048*14*64*2 = 14,680,064 B; k and v 4*2048*2*64*2 = 2,097,152 B; a stats row 458,752 B
    ("flash_attention_fwd", 2, 14_680_064 * 2 + 2_097_152 * 2 + 458_752),
    ("flash_attention_bwd_dq", 3, 14_680_064 * 3 + 2_097_152 * 2 + 458_752 * 2),
    ("flash_attention_bwd_dkv", 4, 14_680_064 * 2 + 2_097_152 * 4 + 458_752 * 2),
])
def test_flash_attention_flops_and_bytes_by_hand(kernel, matmuls, nbytes):
    k = loader.module_from("kernels", "flash_attention")
    shape = k.shape_of(CFG, 4, 2048)
    assert shape == {"batch": 4, "seq": 2048, "heads": 14, "kv_heads": 2, "head_dim": 64, "bytes": 2}
    # one causal [2048, 2048] x 64 matmul over 4 x 14 heads: 4*14*2048*2048*64 = 15,032,385,536
    assert k.flops(kernel, shape) == matmuls * 15_032_385_536
    assert k.bytes_moved(kernel, shape) == nbytes
    peaks = loader.peaks("TPU v5 lite")
    least = k.least_seconds(kernel, shape, peaks)
    assert least == pytest.approx(max(matmuls * 15_032_385_536 / 197e12, nbytes / 819e9))
    assert least == pytest.approx(matmuls * 15_032_385_536 / 197e12)  # compute bound


def test_peaks_table_refuses_an_unlisted_device():
    assert loader.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert loader.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        loader.peaks("TPU v9 imaginary")
