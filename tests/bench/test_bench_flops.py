"""The benchmark's operation and byte counts against hand counts at a small
GPT-2 shape: d=8, d_ff=32, 2 layers, V=50, LoRA r=2 on q and v."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops  # noqa: E402

M = {"d_model": 8, "d_ff": 32, "num_layers": 2, "vocab_size": 50, "max_seq_len": 16,
     "lora": {"rank": 2, "targets": ["q", "v"]}}

# one token through one layer: q, k, v, o (4 × 2·8·8) + up and down
# (2 × 2·8·32) + LoRA x·A and h·B on q and v (2 × (2·8·2 + 2·2·8))
TOKEN_LAYER = 4 * 128 + 2 * 512 + 2 * 64


def test_forward_token_and_last_position_head():
    assert flops.dense_token_flops(M) == TOKEN_LAYER == 1664
    # causal attention over 3 tokens: query t reads t keys, 2·8·t for the
    # scores and 2·8·t for the mix: 32·(1 + 2 + 3)
    assert flops.attention_flops(M, 3) == 192
    # 2 sequences of 3 tokens, head at the last position over all 50 ids
    want = 2 * 2 * (3 * 1664 + 192) + 2 * (2 * 8 * 50)
    assert flops.forward_flops(M, 2, 3, 50) == want == 22336


def test_lora_training_token():
    tokens, layers = 6, 2  # 2 sequences of 3 tokens, head over 5 columns
    forward = layers * tokens * 1664 + layers * 2 * 192 + 2 * 2 * 8 * 5
    # activation gradients: every projection again, less layer 0's inputs
    # of q, k, v (3 × 2·8·8) and of A (2 × 2·8·2); attention twice over
    act_grads = (layers * tokens * 1664 - tokens * (3 * 128 + 2 * 32)
                 + 2 * layers * 2 * 192 + 2 * 2 * 8 * 5)
    # LoRA's weight gradients: dA (2·8·2) and dB (2·2·8) on q and v
    weight_grads = layers * tokens * 2 * (32 + 32)
    assert flops.train_flops(M, 2, 3, 5) == forward + act_grads + weight_grads == 41408


def test_decode_step_with_a_partly_filled_cache():
    # two sequences holding 3 and 5 cached tokens
    per_seq = lambda p: 2 * (1664 + 4 * 8 * (p + 1)) + 2 * 8 * 50  # noqa: E731
    assert flops.decode_step_flops(M, [3, 5]) == per_seq(3) + per_seq(5) == 8896
    # weights: per layer 4·64 + 4·8 (attention), 2·256 + 32 + 8 (MLP),
    # 4·8 (two norms); the tied embedding, one position row, final norm
    weights = 4 * (2 * (256 + 32 + 512 + 40 + 32) + 51 * 8 + 16)
    adapter = 4 * 2 * 2 * (2 * 8 * 2)
    # keys and values of the filled positions read, the new token's
    # read and written: (3 + 2) + (5 + 2) positions × 2 layers × 2 × 8 floats
    kv = 12 * 2 * 2 * 8 * 4
    assert flops.weight_bytes(M) == weights == 8672
    assert flops.adapter_bytes(M) == adapter == 512
    assert flops.decode_step_bytes(M, [3, 5], 1) == weights + adapter + kv == 10720
    # only the filled positions count: a longer cache buffer changes nothing
    assert flops.decode_step_bytes(dict(M, max_seq_len=1024), [3, 5], 1) == 10720


def test_round_is_the_sum_of_its_steps():
    small = dict(M)
    large = dict(M, num_layers=3)
    got = flops.fed_round_flops(small, large, cohort=2, public=4, seq=3, batch=2,
                                local_steps=3, distill_steps=2, server_steps=5,
                                num_classes=7, eval_size=8)
    per_client = (2 * flops.train_flops(small, 4, 3, 50) + 3 * flops.train_flops(small, 2, 3, 7)
                  + flops.forward_flops(small, 4, 3, 50))
    server = 5 * flops.train_flops(large, 4, 3, 50) + flops.forward_flops(large, 4, 3, 50)
    evals = flops.forward_flops(large, 8, 3, 7) + flops.forward_flops(small, 8, 3, 7)
    assert got == 2 * per_client + server + evals
