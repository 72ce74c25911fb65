"""Entry-level example smoke tests (CI tier 1).

The reference ships two minimal user-facing on-ramps
(``/root/reference/examples/image_classifier.py``,
``sentiment_classifier.py``); these drive our counterparts end-to-end
as real subprocesses — one per API style (zero-touch functional
adapter, reference-shaped DSL) — and assert the demo contract: exit 0
and a falling loss.
"""
import os
import re
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, *args):
    env = dict(os.environ,
               JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=8')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'examples', name), *args],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_image_classifier_zero_touch_example():
    out = _run_example('image_classifier.py', '--steps', '12')
    losses = [float(m) for m in
              re.findall(r'train_loss: ([0-9.]+)', out)]
    assert len(losses) == 12, out
    assert min(losses[-3:]) < losses[0], losses


def test_api_reference_generator(tmp_path):
    """`tools/gen_api_docs.py` (the reference docgen pipeline's role)
    renders every public module's docstrings to markdown."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'gen_api_docs.py'),
         str(tmp_path)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    index = (tmp_path / 'index.md').read_text()
    for mod in ('autodist_tpu.api', 'autodist_tpu.strategy.builders',
                'autodist_tpu.parallel.pipeline',
                'autodist_tpu.runtime.session'):
        assert mod in index, index
    api = (tmp_path / 'autodist_tpu_api.md').read_text()
    assert 'class `Trainer`' in api


def test_sentiment_classifier_dsl_example():
    out = _run_example('sentiment_classifier.py', '--steps', '20')
    losses = [float(m) for m in
              re.findall(r'train loss = ([0-9.]+)', out)]
    assert len(losses) >= 2, out
    assert losses[-1] < losses[0], losses
    assert 'emb table: shape (10000, 16)' in out


def test_graft_entry_forward():
    import jax

    import __graft_entry__ as g
    fn, (params, tokens) = g.entry()
    logits = jax.jit(fn)(params, tokens)
    assert logits.shape[0] == tokens.shape[0]
    assert np.isfinite(np.asarray(logits)).all()
