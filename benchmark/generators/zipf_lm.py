"""The benchmark's one data generator: full-length token sequences with
Zipf-distributed ids, as a language-model batch.

A traffic file names it under ``generator`` and gives ``seq``,
``global_batch`` and ``zipf_exponent``; the configuration gives the
vocabulary and the task. Uniform ids would be unlearnable, and the loss
could then check nothing; under a Zipf law the unigram frequencies alone
let it fall within a few steps.

``causal_lm``: ``targets`` are ``tokens`` shifted by one (GPT-2).
``masked_lm``: ``mask_rate`` of the positions show ``mask_token_id`` and
``targets`` are the original ids (BERT's recipe; the program scores
every position).

Everything is drawn from ``numpy.random.default_rng(seed)``: the same
seed gives the same batches, and the probe batches come from a stream of
their own so that their number does not shift the training data.
"""
import numpy as np


def zipf_cdf(vocab, exponent):
    """Cumulative distribution of p(id) ~ 1 / (id + 1) ** exponent."""
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


def batches(traffic, config, seed, batch=None, stream=0):
    """Endless iterator of host batches ``{'tokens', 'targets'}``, int32
    ``[batch, seq]``; ``batch`` defaults to the traffic's global batch."""
    vocab, seq = config['vocab_size'], traffic['seq']
    n = traffic['global_batch'] if batch is None else batch
    task = config['task']
    if task not in ('causal_lm', 'masked_lm'):
        raise ValueError('unknown task %r' % task)
    if seq > config['max_position_embeddings']:
        raise ValueError('seq %d exceeds the %d positions of %s'
                         % (seq, config['max_position_embeddings'],
                            config['name']))
    cdf = zipf_cdf(vocab, traffic['zipf_exponent'])
    rng = np.random.default_rng([seed, stream])
    while True:
        width = seq + 1 if task == 'causal_lm' else seq
        ids = np.searchsorted(cdf, rng.random((n, width)))
        ids = np.minimum(ids, vocab - 1).astype(np.int32)
        if task == 'causal_lm':
            yield {'tokens': ids[:, :-1], 'targets': ids[:, 1:]}
        else:
            masked = rng.random((n, seq)) < config['mask_rate']
            tokens = np.where(masked, np.int32(config['mask_token_id']), ids)
            yield {'tokens': tokens, 'targets': ids}


def tokens_per_step(traffic):
    return traffic['global_batch'] * traffic['seq']
