"""The work counts kept with the benchmark: logical head_dim, listed blocks
only, and no share above 100% for a kernel that runs at its bound."""
import numpy as np
import pytest

import bench_cases  # noqa: F401  (puts the repository on the path)
from bench import flops, spec

dense = spec.part("families", "dense_attention")

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_fwd_counts_unpadded_operands():
    # spion-lra: hd 16 stays 16 however the kernel pads its lanes
    B, H, hd, blk, rows = 128, 4, 16, 64, 32
    blocks = 40
    f, b = flops.fused_fwd(batch=B, heads=H, hd=hd, block=blk, rows=rows,
                           blocks=blocks, dsize=2)
    ng = B * H
    assert f == ng * blocks * (2 * blk * blk * hd) * 2
    q = o = rows * blk * hd * 2
    lse = rows * blk * 4
    kv = blocks * 2 * blk * hd * 2
    assert b == ng * (q + o + lse + kv)
    f128, b128 = flops.fused_fwd(batch=B, heads=H, hd=128, block=blk,
                                 rows=rows, blocks=blocks, dsize=2)
    assert f128 == 8 * f and b128 < 8 * b


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
def test_share_at_the_bound_is_whole(kind):
    kw = dict(batch=8, heads=4, hd=16, block=64, blocks=50, dsize=2)
    if kind == "dkv":
        f, b = flops.fused_dkv(kv_heads=4, cols=32, **kw)
    else:
        f, b = getattr(flops, "fused_" + kind)(rows=32, **kw)
    t = flops.roofline_s(f, b, PEAKS)
    assert t == max(f / PEAKS["flops_bf16"], b / PEAKS["hbm_bytes_per_s"])
    for measured in (t, 1.5 * t, 10 * t):
        assert 100.0 * t / measured <= 100.0


def test_scores_follow_the_plan():
    nv = [np.array([1, 2, 3]), np.array([3, 3, 3])]
    assert flops.valid_blocks(nv[0]) == 6
    assert flops.valid_blocks(nv[0], rows=2) == 3
    assert flops.attention_scores(nv, 64) == (6 + 9) * 64 * 64
    # a row block holding 10 real rows counts 10 rows of its listed blocks
    assert flops.attention_scores([np.array([1, 2])], 64, rows=2,
                                  row_tokens=[64, 10]) == 64 * 64 + 2 * 10 * 64


def test_train_flops_of_the_paper_model():
    m = dense.dims(spec.read_json("bench/configs/spion-lra.json"))
    mm = dense.matmul_params(m)
    assert mm == 4 * (4 * 64 * 64 + 2 * 64 * 256) + 64 * 512
    plan = [np.full(32, 32)] * 4
    f = dense.train_flops_per_step(m, plan, batch=128, seq_len=2048,
                                   block=64)
    att = 3 * 4 * 16 * 4 * 128 * 4 * 2048 * 2048
    assert f == 6 * mm * 128 * 2048 + att


def test_serving_counts():
    m = dense.dims(spec.read_json("bench/configs/qwen2-7b-16l.json"))
    layers = 16 * (2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944)
    assert dense.matmul_params(m) == layers + 3584 * 152064
    w = dense.weight_bytes(m)
    assert 8.0e9 < w < 8.6e9
    nv = [np.ones(18, int)] * 16
    assert dense.decode_cache_bytes(m, nv, [5, 1000], 128) == \
        16 * 2 * (2 * 128 * 4 * 128 * 2)
    p = dense.prefill_flops(m, nv, prompt_len=300, block=128)
    assert p == 2 * layers * 300 + 2 * 3584 * 152064 + \
        4 * 128 * 28 * 16 * (128 + 128 + 44) * 128
