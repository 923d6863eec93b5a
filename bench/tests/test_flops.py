"""bench/flops.py against counts made by hand."""

from bench import flops
from bench.registry import Registry


def test_chatglm_stage7_token_at_5000():
    cfg = Registry().config("chatglm3-6b.stage7")
    # per layer, multiply-adds x 2:
    #   q 4096x4096, k and v 4096x256 each, o 4096x4096  71,303,168
    #   MLP 3 x 4096 x 13696                             336,592,896
    #   indexer q 4096x8192 + k 4096x128                  68,157,440
    #   scores 5000 x 64 x 128 dot, + 64 x 5000 weights   82,560,000
    #   attention over K=2048 rows: 2 x 32 x 128 x 2048 x 2  33,554,432
    layer = 71_303_168 + 336_592_896 + 68_157_440 + 82_560_000 + 33_554_432
    head = 2 * 4096 * 65024                              # 532,676,608
    assert flops.sparse_cell(cfg, 8192)
    assert flops.per_token(cfg, 5000, True) == 7 * layer + head


def test_granite_token_at_300_dense():
    cfg = Registry().config("granite-3.0-1b-a400m")
    # q 1024x1024, k and v 1024x512 each, o 1024x1024: 6,291,456
    # router 1024x32: 65,536; 8 experts x 3 x 1024 x 512 x 2: 25,165,824
    # attention over 300 rows: 2 x 16 x 64 x 300 x 2: 1,228,800
    layer = 6_291_456 + 65_536 + 25_165_824 + 1_228_800
    head = 2 * 1024 * 49155
    assert not flops.sparse_cell(cfg, 1024)
    assert flops.per_token(cfg, 300, False) == 24 * layer + head
