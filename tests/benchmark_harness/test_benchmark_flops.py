"""The analytic operation counts against counts made by hand."""
import json
import os

import pytest

from bench_paths import BENCH

from benchmark.layer_metrics import flash_roofline_pct
from benchmark.models import transformer


def config(name):
    with open(os.path.join(BENCH, 'configs', name + '.json')) as f:
        return json.load(f)


# By hand, BERT-large: 24 layers x 12 x 1024^2 = 301,989,888 parameters
# outside the embeddings, so 603,979,776 FLOPs forward; the tied head is
# 2 x 1024 x 30522 = 62,509,056; QK^T and AV are 4 x 24 x seq x 1024
# (50,331,648 at seq 512, 12,582,912 at seq 128); training is 3 x forward.
# GPT-2 medium: the same blocks, a head of 2 x 1024 x 50257 = 102,926,336
# and, under the causal mask, half of 4 x 24 x 1024 x 1024 = 50,331,648.
@pytest.mark.parametrize('name,seq,by_hand', [
    ('bert-large', 512, 3 * (603979776 + 62509056 + 50331648)),
    ('bert-large', 128, 3 * (603979776 + 62509056 + 12582912)),
    ('gpt2-medium', 1024, 3 * (603979776 + 102926336 + 50331648)),
])
def test_flops_per_token_equal_a_hand_count(name, seq, by_hand):
    assert transformer.flops_per_token(config(name), seq) == by_hand
    assert by_hand in (2150461440, 2037215232, 2271713280)


def test_flash_call_cost_equals_a_hand_count():
    # q, k, v of [96, 16, 512, 64] in bf16: one matmul over the scores is
    # 2 x 96 x 16 x 512 x 512 x 64 = 51,539,607,552 FLOPs and one tensor
    # is 96 x 16 x 512 x 64 x 2 = 100,663,296 bytes
    shape = dict(batch=96, heads=16, seq=512, head_dim=64, itemsize=2)
    assert flash_roofline_pct.call_cost(causal=False, backward=False,
                                        **shape) == (103079215104,
                                                     402653184)
    assert flash_roofline_pct.call_cost(causal=False, backward=True,
                                        **shape) == (257698037760,
                                                     805306368)
    # the causal mask halves the operations, not the bytes
    assert flash_roofline_pct.call_cost(causal=True, backward=False,
                                        **shape) == (51539607552,
                                                     402653184)
