"""The benchmark's operation and byte counts against values worked by
hand at a tiny size."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops  # noqa: E402

# d=8, d_ff=16, 2 layers, patch 2 on 4x4x1: 4 tokens of 4 values
TINY = {"d_model": 8, "d_ff": 16, "num_layers": 2, "patch_size": 2,
        "in_channels": 1, "image_size": 4}


def test_dit_sample_eval_flops_by_hand():
    embed = 2 * 4 * 4 * 8 + 2 * 256 * 8 + 2 * 8 * 8          # 4480
    layer = (2 * 8 * 48          # adaLN: 8 -> 48
             + 3 * 2 * 4 * 8 * 8  # q, k, v
             + 2 * 2 * 4 * 4 * 8  # scores, weighted values
             + 2 * 4 * 8 * 8      # output projection
             + 2 * 2 * 4 * 8 * 16)  # MLP
    final = 2 * 8 * 16 + 2 * 4 * 8 * 4
    assert layer == 5376
    assert flops.dit_sample_eval_flops(TINY) == embed + 2 * layer + final \
        == 15744


def test_dit_b4_is_eleven_gflop_per_sample_eval():
    cfg = {"d_model": 768, "d_ff": 3072, "num_layers": 12, "patch_size": 4,
           "in_channels": 3, "image_size": 32}
    assert abs(flops.dit_sample_eval_flops(cfg) - 11.12e9) < 0.01e9


def test_flash_fwd_cost_by_hand():
    # 2 batch-heads, 3 queries, 5 keys, head dim 4, bf16; o and the f32
    # log-sum-exp row out
    q, kv = ("bf16", (2, 3, 4)), ("bf16", (2, 5, 4))
    results = (("bf16", (2, 3, 4)), ("f32", (2, 3, 1)))
    ops, nbytes = flops.flash_fwd_cost(results, (q, kv, kv))
    assert ops == 4 * 2 * 3 * 5 * 4 == 480
    # q and o: 2*3*4 each; k and v: 2*5*4 each; lse: 2*3 floats
    assert nbytes == (24 + 24 + 40 + 40) * 2 + 4 * 6 == 280
    # leading axes all count as batch-heads
    ops5, _ = flops.flash_fwd_cost(results, (("bf16", (5, 2, 3, 4)),
                                             ("bf16", (5, 2, 5, 4)), kv))
    assert ops5 == 5 * 480


def test_corrector_cost_and_roofline_by_hand():
    # four (2, 5) f32 operands; the (2, 5) result and a (1, 8) f32 partial
    y = ("f32", (2, 5))
    assert flops.corrector_cost((y, ("f32", (1, 8))), (y, y, y, y)) == \
        (50, 5 * 40 + 32)
    assert flops.array_bytes(("f8e4m3fn", (3, 4))) == 12
    peak = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert flops.roofline_seconds(100, 10, peak) == 0.1
    assert flops.roofline_seconds(10, 100, peak) == 0.1
