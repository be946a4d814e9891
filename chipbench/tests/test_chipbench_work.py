"""Work counts from shapes against numbers worked out by hand."""

import os

import pytest

from chipbench_testlib import registry

REG = registry(os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "BENCHMARK.json"))
ARCH = REG.arch("dense_gqa")


def conf(name):
    return REG._json("configs", name)


def test_internvl2_parameter_count():
    # 24 layers x (62,914,560 matrix + 4,096 norm) + 2 x 92,553 x 2,048
    # (embedding, head) + 2,048 (final norm).  PR 11's 1.895 B also held
    # the vision stub's 6,293,504-parameter projector, not run here.
    assert ARCH.param_count(conf("internvl2-2b")) == 1_889_146_880
    assert 1_889_146_880 + 6_293_504 == 1_895_440_384


def test_granite_parameters_per_layer():
    # Granite Code 8B's published widths (its biases aside, which this
    # architecture has not): 4096 x (4096 + 2 x 1024 + 4096) attention
    # + 3 x 4096 x 14336 MLP + two norms
    granite = {"num_hidden_layers": 36, "hidden_size": 4096,
               "intermediate_size": 14336, "num_attention_heads": 32,
               "num_key_value_heads": 8, "head_dim": 128,
               "vocab_size": 49152, "rope_theta": 1e7, "rms_norm_eps": 1e-5}
    m = ARCH.dims(granite)
    assert ARCH.layer_matmul_params(m) + 2 * m.d == 218_112_000
    assert ARCH.kv_bytes_per_token(granite) == 36 * 4096


def test_decode_work_by_hand():
    # one live row at context 10: 2 x (layer matrices + head) FLOPs plus
    # 4 x 16 x 128 x 24 x 10 of attention; every weight once, one embedding
    # row, and 10 tokens of K/V (9 read, 1 written) at 98,304 B a token
    flops, nbytes = ARCH.decode_work(conf("internvl2-2b"), [10])
    assert flops == 3_400_962_048
    assert nbytes == 3_400_183_808


def test_chunk_work_by_hand():
    # the first 128-token chunk of a longer prompt: no head, causal
    # attention over 1 + 2 + ... + 128 = 8,256 query-key pairs per head
    flops, nbytes = ARCH.chunk_work(conf("internvl2-2b"), 0, 128, False)
    assert flops == 388_170_252_288
    assert nbytes == 3_033_202_688
    f2, b2 = ARCH.chunk_work(conf("internvl2-2b"), 128, 128, True)
    assert f2 - flops == pytest.approx(
        196_608 * 128 * 128 + 2 * 2048 * 92553)
    assert b2 - nbytes == 128 * 98_304 + (2048 * 92553 + 2048) * 2


def test_peaks_and_device_kinds():
    assert REG.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert REG.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
