"""Work counts of bench/work.py against hand arithmetic."""

import dataclasses

import pytest

from bench import model, peaks, work

QWEN = model.arch("qwen3-1.7b", model.load("qwen3-1.7b"))
#: the same widths with an untied head (the program's own qwen3-1.7b)
QWEN_UNTIED = dataclasses.replace(QWEN, tie_embeddings=False)
DSK = model.arch("deepseek-moe-16b-pp4", model.load("deepseek-moe-16b-pp4"))
V5E = peaks.PEAKS["TPU v5 lite"]


def test_qwen3_parameter_bytes_are_4_06_gb():
    # untied: 4.06 GB; the published config ties the head to the embedding
    d, v, f = 2048, 151936, 6144
    attn = d * 2048 + 2 * d * 1024 + 2048 * d      # wq, wk, wv, wo
    per_layer = attn + 3 * d * f + 2 * d + 2 * 128  # + norms, qk-norms
    n = 28 * per_layer + 2 * v * d + d              # + embedding, head, final norm
    assert work.param_bytes(QWEN_UNTIED) == 2 * n
    assert round(work.param_bytes(QWEN_UNTIED) / 1e9, 2) == 4.06
    assert work.param_bytes(QWEN) == 2 * (n - v * d)
    assert round(work.param_bytes(QWEN) / 1e9, 2) == 3.44


@pytest.mark.parametrize("arch", [QWEN, QWEN_UNTIED], ids=["tied", "untied"])
def test_qwen3_decode_step_reads_every_weight_but_the_unread_embedding_rows(arch):
    # a decode step of 8 tokens reads 8 embedding rows, not the 0.62 GB
    # table; a tied head reads the whole table once, rows included
    flops, nbytes = work.step_work(arch, [0] * 8, [1] * 8, [True] * 8)
    table = 151936 * 2048 * 2
    kv_new = 8 * 28 * 2 * 8 * 128 * 2
    logits = 8 * 151936 * 4
    if arch.tie_embeddings:
        assert nbytes == work.param_bytes(arch) + kv_new + logits
    else:
        assert nbytes == (work.param_bytes(arch) - table + 8 * 2048 * 2
                          + kv_new + logits)
    assert round((work.param_bytes(QWEN_UNTIED) - table) / 1e9, 2) == 3.44


def test_a_tied_step_that_yields_no_token_reads_only_its_embedding_rows():
    _, tied = work.step_work(QWEN, [0] * 2, [64, 64], [False, False])
    _, untied = work.step_work(QWEN_UNTIED, [0] * 2, [64, 64], [False, False])
    assert tied == untied
    _, with_head = work.step_work(QWEN, [0] * 2, [64, 64], [False, True])
    assert with_head - tied == 151936 * 2048 * 2 + 151936 * 4 - 128 * 2048 * 2


def test_moe_counts_top_k_routed_experts_per_layer():
    d, f = 2048, 1408
    expert = 3 * d * f * 2                            # one expert, bf16
    routed_all = 6 * 64 * expert
    got = work.layer_weight_bytes(DSK)
    full = work.param_bytes(DSK) - 2 * 102400 * d * 2 - d * 2
    # the least count drops (64 - 6) routed experts in each of 6 MoE layers
    assert full - got == routed_all - 6 * 6 * expert
    # and no routing of 8 tokens can touch fewer than top_k experts a layer
    assert got < full


def test_flops_count_fed_tokens_and_the_head_only_on_yielding_rows():
    d, v = 2048, 151936
    f0, _ = work.step_work(QWEN, [100, 0, 7], [64, 0, 1], [False, False, True])
    body = sum(work.token_flops(QWEN, 100 + c) for c in range(64))
    body += work.token_flops(QWEN, 7)
    assert f0 == body + 2 * d * v
    f1, _ = work.step_work(QWEN, [100, 0, 7], [64, 0, 1], [True, False, True])
    assert f1 - f0 == 2 * d * v
    # an idle slot's padding and position change nothing
    f2, _ = work.step_work(QWEN, [100, 999, 7], [64, 0, 1], [False, True, True])
    assert f2 == f0


def test_attention_flops_grow_with_the_live_prefix():
    per_key = 4 * 16 * 128 * 28
    assert work.token_flops(QWEN, 10) - work.token_flops(QWEN, 9) == per_key


@pytest.mark.parametrize("arch", [QWEN, QWEN_UNTIED, DSK],
                         ids=["qwen3", "qwen3-untied", "deepseek-moe"])
@pytest.mark.parametrize("feed", [
    ([0] * 8, [1] * 8, [True] * 8),
    ([512] * 8, [64] * 8, [False] * 7 + [True]),
    ([1500, 0, 0, 0, 0, 0, 0, 3], [1, 0, 0, 0, 0, 0, 0, 64], [True] + [False] * 7),
], ids=["decode", "full-prefill", "mixed"])
def test_no_share_passes_100_when_the_step_takes_its_least_time(arch, feed):
    flops, nbytes = work.step_work(arch, *feed)
    least = work.least_seconds(flops, nbytes, V5E)
    roofline = 100 * least / least
    mfu = 100 * flops / (least * V5E.bf16_flops)
    assert roofline == 100.0
    assert 0 < mfu <= 100.0
    # and the bound that sets it is one of the two
    assert least in (flops / V5E.bf16_flops, nbytes / V5E.hbm_bytes_s)
