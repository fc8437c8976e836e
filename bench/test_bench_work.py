"""bench/work.py's counts for both configurations against numbers worked
out by hand from the published widths (qwen2-7b at the 22 layers held)."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import work  # noqa: E402


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_qwen3_4b_counts():
    c = conf("qwen3-4b")
    # d 2560, q 32*128 = 4096, kv 8*128 = 1024, d_ff 9728, 36 layers
    assert work.layer_matmul_params(c) == (
        2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    ) == 100925440
    assert work.token_matmul_flops(c) == 2 * 36 * 100925440 == 7266631680
    assert work.head_flops(c) == 2 * 2560 * 151936 == 777912320
    assert work.attention_flops(c, 1000) == 4 * 36 * 32 * 128 * 1000
    # weights: 36 x (matmuls + 2 norms + q/k norm) + final norm + untied head
    assert work.weight_bytes(c) == 2 * (
        36 * (100925440 + 2 * 2560 + 2 * 128) + 2560 + 2560 * 151936
    ) == 8044936192
    assert work.kv_bytes_per_position(c) == 2 * 36 * 1024 * 2 == 147456
    flops, nbytes = work.decode_call(c, [100, 200])
    assert flops == 2 * (7266631680 + 777912320) + 4 * 36 * 32 * 128 * 300
    assert nbytes == 8044936192 + 2 * 2560 * 2 + 300 * 147456 == 8089183232


def test_qwen2_7b_counts():
    c = conf("qwen2-7b")
    # d 3584, q 28*128 = 3584, kv 4*128 = 512, d_ff 18944, 22 layers
    assert work.layer_matmul_params(c) == (
        3584 * 3584 + 2 * 3584 * 512 + 3584 * 3584 + 3 * 3584 * 18944
    ) == 233046016
    assert work.token_matmul_flops(c) == 2 * 22 * 233046016
    assert work.head_flops(c) == 2 * 3584 * 152064
    # QKV bias: 3584 + 2 * 512 per layer; no q/k norm
    assert work.weight_bytes(c) == 2 * (
        22 * (233046016 + 2 * 3584 + 3584 + 1024) + 3584 + 3584 * 152064
    ) == 11344544768
    assert work.kv_bytes_per_position(c) == 2 * 22 * 512 * 2
    # the second 1024-token chunk of a prompt, which completes it: causal
    # contexts 1025 .. 2048 sum to 1024 * 1024 + 1024 * 1025 / 2
    got = work.prefill_chunk_flops(c, 1024, 1024, True)
    assert got == (1024 * 2 * 22 * 233046016 + 4 * 22 * 28 * 128 * 1573376
                   + 2 * 3584 * 152064) == 10997441495040
    assert work.prefill_chunk_flops(c, 0, 1024, False) == (
        1024 * 2 * 22 * 233046016 + 4 * 22 * 28 * 128 * (1024 * 1025 // 2)
    )


def test_roofline_picks_the_binding_side():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_s(197e12, 1.0, peak) == 1.0          # compute bound
    assert work.roofline_s(1.0, 819e9 * 2, peak) == 2.0       # memory bound
    # a qwen3-4b decode step of 16 rows at 512 positions reads its weights:
    # memory bound by more than an order of magnitude
    c = conf("qwen3-4b")
    f, b = work.decode_call(c, [512] * 16)
    assert b / peak["hbm_bytes_per_s"] > 10 * f / peak["bf16_flops_per_s"]
