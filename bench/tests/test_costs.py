"""Operation and byte counts of the metric files, on hand-worked shapes."""

import math

from bench import costs as C


def tiny():
    return {"num_hidden_layers": 2, "hidden_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "intermediate_size": 256, "vocab_size": 1000,
            "tie_word_embeddings": True,
            "adapters": {"quanta": {
                "pairs": [[1, 2], [0, 2], [0, 1]],
                "targets": {"q_proj": {"dims_in": [8, 4, 4],
                                       "dims_out": [8, 4, 4]}}}}}


def test_projection_counts():
    m = tiny()
    # per layer: q 128x128, k 128x64, v 128x64, o 128x128,
    # gate/up 128x256, down 256x128
    per_layer = 128 * 128 * 2 + 128 * 64 * 2 + 128 * 256 * 3
    assert C.proj_params(m) == 2 * per_layer
    flops, nbytes = C.quantized_matmul_step(m, rows=3)
    assert flops == 2 * 3 * 2 * per_layer
    scales = 2 * sum(math.ceil(a / 64) * b * 4 for a, b in [
        (128, 128), (128, 64), (128, 64), (128, 128), (128, 256),
        (128, 256), (256, 128)])
    acts = 2 * 3 * 2 * sum(a + b for a, b in [
        (128, 128), (128, 64), (128, 64), (128, 128), (128, 256),
        (128, 256), (256, 128)])
    assert nbytes == 2 * per_layer / 2 + scales + acts


def test_paged_decode_counts():
    m = tiny()
    flops, nbytes = C.paged_decode_step(m, live_tokens=100, rows=2)
    # K and V of 100 tokens: 2 layers x 2 x 2 kv heads x 32 x 2 bytes
    assert nbytes == 100 * (2 * 2 * 2 * 32 * 2) + 2 * 2 * 128 * 2 * 2
    assert flops == 4 * 100 * 128 * 2


def test_flash_forward_counts():
    m = tiny()
    flops, nbytes = C.flash_forward_call(m, rows=1, seq=4)
    assert flops == 4 * 4 * 32 * (4 * 5 / 2)
    assert nbytes == 2 * 4 * 32 * (2 * 4 + 2 * 2)


def test_quanta_chain_and_train_flops():
    # (8,4,4): pair (1,2) -> tensor (4,4,4,4), 8 rows of 16: 2*8*256
    # pair (0,2) -> (8,4,8,4), 4 rows of 32: 2*4*1024; (0,1) likewise
    assert C.quanta_chain_flops([8, 4, 4], [8, 4, 4],
                                [[1, 2], [0, 2], [0, 1]]) == \
        2 * 8 * 256 + 2 * 4 * 1024 * 2
    m = tiny()
    chain = C.quanta_chain_flops([8, 4, 4], [8, 4, 4],
                                 [[1, 2], [0, 2], [0, 1]])
    want = (4 * C.proj_params(m) + 4 * 128 * 1000
            + 12 * 2.5 * 4 * 32 * 2 + 5 * chain * 2)
    assert C.train_flops_per_token(m, seq=4) == want


def test_seconds_names_the_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert C.seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert C.seconds(10.0, 1000.0, peaks) == (100.0, "memory")
