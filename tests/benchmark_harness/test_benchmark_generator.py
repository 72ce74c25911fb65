"""The data generator: seeded, learnable, shaped as the task says."""
import itertools

import numpy as np
import pytest

from bench_paths import tiny_config

from benchmark.generators import zipf_lm

TRAFFIC = dict(generator='zipf_lm', seq=32, global_batch=64,
               zipf_exponent=1.1)


def take(config, seed, n=2, **kw):
    return list(itertools.islice(
        zipf_lm.batches(TRAFFIC, config, seed, **kw), n))


@pytest.mark.parametrize('causal', [True, False], ids=['causal', 'masked'])
def test_same_seed_same_batches_other_seed_other_batches(causal):
    config = tiny_config(causal)
    a, b, c = take(config, 7), take(config, 7), take(config, 8)
    for x, y in zip(a, b):
        assert np.array_equal(x['tokens'], y['tokens'])
        assert np.array_equal(x['targets'], y['targets'])
    assert not np.array_equal(a[0]['tokens'], c[0]['tokens'])
    assert not np.array_equal(a[0]['tokens'], a[1]['tokens'])   # fresh
    assert a[0]['tokens'].shape == (64, 32)
    assert a[0]['tokens'].dtype == np.int32
    # the probe's stream does not shift the training data
    probe = take(config, 7, n=1, batch=2, stream=1)[0]
    assert probe['tokens'].shape == (2, 32)
    assert not np.array_equal(probe['tokens'], a[0]['tokens'][:2])


def test_causal_targets_are_the_tokens_shifted_by_one():
    batch = take(tiny_config(True), 1, n=1)[0]
    assert np.array_equal(batch['tokens'][:, 1:], batch['targets'][:, :-1])


def test_masked_lm_shows_the_mask_id_at_15_percent_of_positions():
    config = tiny_config(False)
    batch = take(config, 1, n=1)[0]
    changed = batch['tokens'] != batch['targets']
    assert (batch['tokens'][changed] == config['mask_token_id']).all()
    # 2048 positions at rate 0.15, less the ones whose id is the mask id
    assert 0.10 < changed.mean() < 0.20


def test_ids_follow_a_zipf_law_inside_the_vocabulary():
    ids = np.concatenate([b['targets'].ravel()
                          for b in take(tiny_config(False), 3, n=8)])
    assert ids.min() >= 0 and ids.max() < 256
    counts = np.bincount(ids, minlength=256)
    assert counts[0] == counts.max()
    # p(1) / p(2) = 2 ** 1.1 = 2.14
    assert 1.7 < counts[0] / counts[1] < 2.7
    cdf = zipf_lm.zipf_cdf(256, 1.1)
    assert cdf[-1] == 1.0 and np.all(np.diff(cdf) > 0)


def test_a_sequence_longer_than_the_positions_is_refused():
    with pytest.raises(ValueError, match='positions'):
        next(zipf_lm.batches(dict(TRAFFIC, seq=64), tiny_config(True), 0))
