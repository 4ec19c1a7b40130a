"""The analytic operation and byte counts against hand-worked values for
the two configurations, and the traffic generator's invariants."""

import json
import os

import pytest

from benchmark.lib import traffic, work

from benchmark.lib.manifest import BENCH_DIR as BENCH


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


GPT2, BERT = _cfg("gpt2-medium"), _cfg("bert-large")


def test_matmul_params_by_hand():
    # per layer 4*1024^2 (qkv, out) + 2*1024*4096 (ffn) = 12,582,912
    assert work.matmul_params(GPT2) == 24 * 12582912 + 1024 * 50257
    assert work.matmul_params(GPT2) == 353453056
    assert work.matmul_params(BERT) == 24 * 12582912 + 1024 * 30522
    assert work.matmul_params(BERT) == 333244416


def test_train_flops_per_token_by_hand():
    # GPT-2 medium at 512 tokens: matrices 2*353,453,056 = 706,906,112 a
    # token forward; attention 24 layers * 4*512*512*1024 * 513/1024 kept
    # = 12,910,067,712 a sequence = 25,214,976 a token; x3 for the backward
    assert work.forward_flops_per_seq(GPT2, 512) == pytest.approx(
        706906112 * 512 + 12910067712)
    assert work.train_flops_per_token(GPT2, 512) == pytest.approx(
        3 * (706906112 + 25214976))
    # BERT-large keeps the whole score matrix: 24*4*512*512*1024/512 a token
    assert work.train_flops_per_token(BERT, 512) == pytest.approx(
        3 * (2 * 333244416 + 24 * 4 * 512 * 1024))


@pytest.mark.parametrize("kernel, products, tensors", [
    ("flash_fwd", 2, 4), ("flash_dq", 3, 6), ("flash_dkv", 4, 7)])
def test_flash_call_work_by_hand(kernel, products, tensors):
    # 8 sequences of 512 tokens, 16 heads of 64: one S x S x 64 product over
    # all heads is 2*8*512*512*1024 = 4,294,967,296 FLOPs
    flops, nbytes = work.flash_call_work(BERT, kernel, 8, 512)
    assert flops == products * 4294967296
    assert nbytes == tensors * 8 * 512 * 1024 * 2
    causal, _ = work.flash_call_work(GPT2, kernel, 8, 512)
    assert causal == pytest.approx(flops * 513 / 1024)


def test_roofline_takes_the_larger_bound():
    assert work.roofline_seconds(197e12, 1.0, 197e12, 819e9) == 1.0
    assert work.roofline_seconds(1.0, 819e9, 197e12, 819e9) == 1.0


def test_decode_step_bytes_by_hand():
    assert work.kv_bytes_per_token(GPT2) == 2 * 24 * 1024 * 2 == 98304
    assert work.decode_step_bytes(GPT2, 1000) == (
        353453056 * 2 + 1000 * 98304)


def test_serve_flops_count_every_token_against_its_context():
    # 3 prompt tokens and 2 outputs: 4 tokens go through the model (the
    # last output is never fed back); contexts 1+2+3+4 = 10 = 4*4*(5/8)
    one_layer = dict(GPT2, num_layers=1)
    assert work.serve_flops(one_layer, 3, 2) == pytest.approx(
        2 * work.matmul_params(one_layer) * 4 + 4 * 10 * 1024)


def _mix():
    with open(os.path.join(BENCH, "traffic", "serve-chat-poisson.json")) as f:
        return json.load(f)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    mix = _mix()
    a = traffic.open_loop_schedule(mix, 50257, 1, 45.0)
    b = traffic.open_loop_schedule(mix, 50257, 2**31 + 5, 45.0)
    assert len(a) == len(b) == round(mix["rate_rps"] * 45)
    prompt_len = lambda r: len(r["tokens"])
    assert sorted(map(prompt_len, a)) == sorted(map(prompt_len, b))
    assert list(map(prompt_len, a)) != list(map(prompt_len, b))
    assert a[0]["tokens"] != b[0]["tokens"]
    # the skeleton (when each request is due, how much it asks for) is the
    # mix's, the same for every seed
    assert [(r["due"], r["max_tokens"]) for r in a] == [
        (r["due"], r["max_tokens"]) for r in b]
    assert 0 < a[0]["due"] and a[-1]["due"] < 45.0
    assert traffic.open_loop_schedule(mix, 50257, 1, 45.0) == a


def test_prompt_lengths_are_distinct_and_inside_the_bounds():
    mix = _mix()
    avoid = {16, 32, 64, 128, 256, 512}
    lens = traffic.lengths(mix["prompt_len"], 90, avoid)
    assert len(set(lens)) == 90 and not set(lens) & avoid
    assert min(lens) >= 16 and max(lens) <= 768
    median = sorted(lens)[45]
    assert 120 <= median <= 136
    outs = traffic.lengths(mix["output_len"], 90)
    assert min(outs) >= 8 and max(outs) <= 192


def test_prefill_buckets_follow_the_engine_rule():
    assert traffic.prefill_buckets([5, 16, 17, 700], 8, 1024) == [
        8, 16, 32, 1024]
    assert traffic.prefill_buckets([1500], 8, 1024) == [512, 1024]
