"""Granite Code 8B's work counts (``arch/dense_gqa_bias.py``) against
numbers worked out by hand."""

import os

import pytest

from chipbench_testlib import registry

REG = registry(os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "BENCHMARK.json"))
ARCH = REG.arch("dense_gqa_bias")
GRANITE = REG._json("configs", "granite-8b")


def test_parameters_held():
    # 18 layers x (218,103,808 matrices + 43,008 biases + 8,192 norms)
    # + 49,152 x 4,096 tied embedding + 4,096 final norm
    assert 218_103_808 + 43_008 + 8_192 == 218_155_008
    assert ARCH.param_count(GRANITE) == \
        18 * 218_155_008 + 201_326_592 + 4_096 == 4_128_120_832


def test_parameters_at_published_depth():
    # all 36 layers: Granite Code 8B's 8.05 B
    assert ARCH.param_count(dict(GRANITE, num_hidden_layers=36)) == \
        8_054_910_976


def test_kv_bytes_per_token():
    # 18 layers x K and V x 8 heads x 128 x 2 bytes
    assert ARCH.kv_bytes_per_token(GRANITE) == 73_728


def test_decode_work_by_hand():
    # one live row at context 10: 2 x (18 x 218,103,808 matrices + the
    # tied head's 4,096 x 49,152) + 18 x 43,008 bias adds + 4 x 32 x 128
    # x 18 x 10 of attention; bytes: every layer parameter, the table
    # once and the final norm (8,256,241,664), one embedding row (8,192)
    # and 10 tokens of K/V (9 read, 1 written)
    flops, nbytes = ARCH.decode_work(GRANITE, [10])
    assert flops == 8_254_390_272 + 774_144 + 2_949_120 == 8_258_113_536
    assert nbytes == 8_256_241_664 + 8_192 + 737_280 == 8_256_987_136


def test_chunk_work_by_hand():
    # the first 128-token chunk of a longer prompt: no head, biases added
    # at every position, causal attention over 8,256 query-key pairs per
    # head; bytes: the layers (7,853,580,288), 128 embedding rows and the
    # chunk's K/V written
    flops, nbytes = ARCH.chunk_work(GRANITE, 0, 128, False)
    assert flops == 128 * (7_851_737_088 + 774_144) + 294_912 * 8_256 \
        == 1_007_556_231_168
    assert nbytes == 7_853_580_288 + 1_048_576 + 9_437_184 == 7_864_066_048
    # the next chunk completes the prompt: 128 more keys per query, the
    # prefix's K/V read, and the tied table read once by the head
    f2, b2 = ARCH.chunk_work(GRANITE, 128, 128, True)
    assert f2 - flops == pytest.approx(294_912 * 128 * 128
                                       + 2 * 4_096 * 49_152)
    assert b2 - nbytes == 128 * 73_728 + (4_096 * 49_152 + 4_096) * 2


def test_weights_are_the_programs_tree():
    """Bias leaves of the program's shapes, and no ``head`` leaf."""
    import jax
    w = jax.eval_shape(lambda k: ARCH.make_weights(GRANITE, k),
                       jax.random.key(0))
    assert "head" not in w
    units = w["units"]["b0"]
    assert {k: units[k].shape for k in ("bq", "bk", "bv", "bo", "b_gate",
                                        "b_up", "b_down")} == {
        "bq": (18, 4096), "bk": (18, 1024), "bv": (18, 1024),
        "bo": (18, 4096), "b_gate": (18, 14336), "b_up": (18, 14336),
        "b_down": (18, 4096)}
    assert sum(x.size for x in jax.tree.leaves(w)) == 4_128_120_832
