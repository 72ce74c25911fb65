"""Block-diffusion language-model batches (BD3-LM, arXiv:2503.09573;
SDAR, arXiv:2510.06303): full-length sequences of Zipf-distributed ids
``x_0``, a noised copy ``x_t`` of each, and the loss's weights.

A traffic file names it under ``generator`` and gives ``seq``,
``global_batch``, ``block_length``, ``t_low``, ``t_high`` and
``zipf_exponent``; the configuration gives the vocabulary slice, whose
LAST row is the mask id (``mask_token_id``; ids are drawn below it), and
the block length the model was built with (the two have to agree).

A batch is ``{'tokens': x_t, 'targets': x_0, 'mask': w}``, int32, int32
and float32 ``[batch, seq]``:

* every block of ``block_length`` positions of every sequence has a
  noise level ``t`` in ``[t_low, t_high)``: BD3-LM's clipped range for
  small blocks, STRATIFIED over the ``K`` blocks of the batch (the
  family's antithetic draw): ``t_k = t_low + (t_high - t_low) ((u + k /
  K) mod 1)`` with one ``u`` a batch, the levels dealt to the blocks in
  a seeded permutation;
* each position of a block shows the mask id with probability ``t``,
  independently (the linear schedule, ``alpha_t = 1 - t``), else its own
  id;
* ``w = 1 / t`` of its block where the position shows the mask id, else
  0: the loss is ``sum(w nll) / sum(w)`` at the same position.

Everything is drawn from ``numpy.random.default_rng([seed, stream])``:
the same seed gives the same batches, and the probe batches come from a
stream of their own.
"""
import numpy as np

from benchmark.generators.zipf_lm import zipf_cdf


def noise_levels(rng, blocks, t_low, t_high):
    """One noise level a block, stratified over ``blocks`` and dealt in
    a random order."""
    strata = (rng.random() + np.arange(blocks) / blocks) % 1.0
    return (t_low + (t_high - t_low) * rng.permutation(strata))


def batches(traffic, config, seed, batch=None, stream=0):
    """Endless iterator of host batches ``{'tokens', 'targets',
    'mask'}``; ``batch`` defaults to the traffic's global batch."""
    seq, block = traffic['seq'], traffic['block_length']
    n = traffic['global_batch'] if batch is None else batch
    mask_id = config['mask_token_id']
    if config['task'] != 'block_diffusion_lm':
        raise ValueError('unknown task %r' % config['task'])
    if block != config['block_length'] or seq % block:
        raise ValueError('blocks of %d positions over seq %d, for a model '
                         'built with block_length %r'
                         % (block, seq, config['block_length']))
    if seq > config['max_position_embeddings']:
        raise ValueError('seq %d exceeds the %d positions of %s'
                         % (seq, config['max_position_embeddings'],
                            config['name']))
    cdf = zipf_cdf(mask_id, traffic['zipf_exponent'])   # ids below the mask
    rng = np.random.default_rng([seed, stream])
    while True:
        ids = np.searchsorted(cdf, rng.random((n, seq)))
        ids = np.minimum(ids, mask_id - 1).astype(np.int32)
        t = noise_levels(rng, n * seq // block, traffic['t_low'],
                         traffic['t_high'])
        t = np.repeat(t.reshape(n, seq // block), block, axis=1)
        masked = rng.random((n, seq)) < t
        yield {'tokens': np.where(masked, np.int32(mask_id), ids),
               'targets': ids,
               'mask': np.where(masked, 1.0 / t, 0.0).astype(np.float32)}


def tokens_per_step(traffic):
    """The clean tokens: a token that is trained on counts once, though
    the stack runs two rows for it."""
    return traffic['global_batch'] * traffic['seq']
