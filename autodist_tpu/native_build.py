"""On-demand builder for the framework's native (C++) components.

Sources live in ``autodist_tpu/native/`` (inside the package so installed
wheels ship them); binaries/libraries are cached under
``/tmp/autodist-tpu/native/<source-hash>/`` so rebuilds happen only when
the source changes. Uses plain g++ (present in the supported images); a
``make``-based flow is equivalent (see autodist_tpu/native/Makefile).
"""
import hashlib
import os
import subprocess
import tempfile

from autodist_tpu.const import DEFAULT_WORKING_DIR
from autodist_tpu.utils import logging

NATIVE_SRC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              'native')
NATIVE_CACHE_DIR = os.path.join(DEFAULT_WORKING_DIR, 'native')


def _src_path(name):
    return os.path.join(NATIVE_SRC_DIR, name)


def build(source_name, output_name=None, shared=False, extra_flags=()):
    """Compile ``native/<source_name>`` and return the artifact path."""
    src = _src_path(source_name)
    # -O3: the data-plane element loops (bf16 wire conversion, BADD
    # accumulate, BSTEP update rules) need the auto-vectorizer, which
    # gcc enables only at -O3; at -O2 the scalar bf16 loop was slow
    # enough to erase the wire-byte saving under multi-worker
    # contention (BASELINE.md bf16 row).
    cmd = ['g++', '-O3', '-std=c++17', '-pthread']
    if shared:
        cmd += ['-shared', '-fPIC']
    cmd += list(extra_flags)
    # cache key = source bytes AND the compile command: a flag change
    # must rebuild byte-identical sources (a warm cache otherwise
    # silently pins old-flag binaries forever)
    h = hashlib.sha256()
    with open(src, 'rb') as f:
        h.update(f.read())
    h.update('\x00'.join(cmd).encode())
    digest = h.hexdigest()[:16]
    out_name = output_name or os.path.splitext(source_name)[0]
    if shared:
        out_name += '.so'
    out_dir = os.path.join(NATIVE_CACHE_DIR, digest)
    out = os.path.join(out_dir, out_name)
    if os.path.exists(out):
        return out
    os.makedirs(out_dir, exist_ok=True)
    # Compile under a directory of this call's own and rename onto
    # ``out``: callers that race (several workers on a cold host) each
    # link their own file, so whatever exists at ``out`` is whole and
    # never open for writing (executing a file the linker still holds
    # fails with ETXTBSY); a failed compile leaves nothing there.
    with tempfile.TemporaryDirectory(dir=out_dir) as tmp_dir:
        tmp = os.path.join(tmp_dir, out_name)
        cmd = cmd + [src, '-o', tmp]
        logging.info('Building native component: %s', ' '.join(cmd))
        subprocess.run(cmd, check=True)
        os.replace(tmp, out)
    return out
