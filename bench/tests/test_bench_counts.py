"""Each operation and byte counter against a count made by hand at one
small shape, and the peaks table."""

import pytest

from bench import counts
from bench.reference import gqa, mla

GQA = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "vocab_size": 32,
       "num_hidden_layers": 1}
MLA = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
       "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
       "intermediate_size": 16, "vocab_size": 32, "num_hidden_layers": 1}
PEAK = counts.peaks("TPU v5 lite")


def test_gqa_counts_by_hand():
    # q, o: 8*2*4 each; k, v: 8*1*4 each; MLP 3*8*16
    assert gqa.layer_params(GQA) == 64 + 64 + 32 + 32 + 384
    # 4 tokens: products 2*576*4, causal pairs 4*5/2 = 10 at 2*2*2*4 each,
    # the head at one position 2*8*32
    assert gqa.prefill_flops(GQA, 1, 4) == 4608 + 320 + 512
    # one step over 5 keys: 2*576 + 2*2*2*4*5 + 2*8*32; bytes: bf16 layer
    # (576 + two norms of 8) + head 256 + final norm 8, and 5 tokens of K
    # and V (2 * 1 head * 4 * 2 B)
    assert gqa.decode_step(GQA, 1, 5) == (1152 + 160 + 512,
                                          2 * (592 + 256 + 8) + 5 * 16)


def test_mla_counts_by_hand():
    # wq_a 32, wq_b 4*2*4, wkv_a 8*6, wkv_b 4*2*6, wo 2*4*8, MLP 384
    assert mla.layer_params(MLA) == 32 + 32 + 48 + 48 + 64 + 384
    # pairs 10 at 2*2*(2 + 2 + 4); head 512
    assert mla.prefill_flops(MLA, 1, 4) == 2 * 608 * 4 + 320 + 512
    # absorbed step: products 2*(32 + 32 + 48 + 2*2*4 + 2*4*4 + 64 + 384),
    # scores and context 2*2*(4 + 2 + 4)*5, head 512; bytes: layer + norms
    # (2*8 + 4 + 4) + head + final norm, latent 5 tokens * (4 + 2) * 2 B
    assert mla.decode_step(MLA, 1, 5) == (2 * 608 + 200 + 512,
                                          2 * (632 + 256 + 8) + 5 * 12)


def test_codec_bytes_by_hand():
    # 3000 elements fill 3 chunks of 1024: 2 B read, 1.5 B written each,
    # plus per chunk 64 slots of 3 B and a 4 B count
    assert counts.encode_bytes(3000, chunk=1024, cap=64) == \
        3 * 1024 * 3.5 + 3 * 196
    assert counts.decode_bytes(3000, chunk=1024, cap=64) == \
        counts.encode_bytes(3000, chunk=1024, cap=64)


def test_paged_attention_by_hand():
    assert counts.page_bytes(1024, 8) == 1024 + 512 + 24 + 4
    # rows of 1 and 2 pages of 16 tokens: 48 keys at 2*2*(4 + 4); bytes:
    # 3 page pairs, bf16 queries 2*2*4*2, f32 partials 2*2*(4 + 2)*4
    flops, nbytes = counts.paged_attention_step(
        rows=2, heads=2, head_dim=4, dv=4, full_pages=[1, 2],
        tokens_per_page=16, page_bytes_kv=100)
    assert (flops, nbytes) == (1536, 300 + 32 + 96)


def test_roofline_and_peaks():
    assert counts.roofline_s(197e12, 0, PEAK) == (1.0, "compute")
    assert counts.roofline_s(0, 819e9, PEAK) == (1.0, "memory")
    with pytest.raises(KeyError):
        counts.peaks("cpu")
