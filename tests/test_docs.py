"""The documents name only files that exist.

A document outlives the code it describes unless something holds it:
every back-quoted repository path in the README, the design and usage
pages and the verify notes must exist in the checkout. ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` are history and name what is gone on
purpose; they are not held.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, 'autodist_tpu')

DOCUMENTS = ['README.md', 'docs/index.md'] + sorted(
    glob.glob('docs/design/*.md', root_dir=REPO) +
    glob.glob('docs/usage/*.md', root_dir=REPO)) + \
    ['.claude/skills/verify/SKILL.md']

#: Names the documents give to files their READER is to write.
READERS_OWN = {'train.py', 'my_training_script.py', 'your_driver.py'}

_QUOTED = re.compile(r'```.*?```|`[^`\n]+`', re.S)
_SUFFIX = re.compile(r'(::.*|:\d+(-\d+)?(,\d+(-\d+)?)*)$')   # :12-34, ::test
_SOURCE = re.compile(r'[\w\-][\w.\-]*\.(py|cc|md|json|jsonl)')


def _dirs(root):
    return {n for n in os.listdir(root)
            if os.path.isdir(os.path.join(root, n))}


@functools.lru_cache(maxsize=None)
def _tree():
    """(top-level directories, the package's directories, every file
    name) of the checkout, without what ``.gitignore`` lists as a
    directory: a copy of an older commit kept there names what is gone."""
    with open(os.path.join(REPO, '.gitignore')) as f:
        ignored = {line.strip().rstrip('/') for line in f
                   if line.strip().endswith('/')} | {'.git'}
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in ignored]
        names.update(files)
    return _dirs(REPO) - ignored, _dirs(PACKAGE) - ignored, names


def _exists(root, word):
    """``word`` under ``root``, as a path or as ``dir/module.attr``."""
    head, stem = os.path.split(word)
    return os.path.exists(os.path.join(root, word)) or os.path.exists(
        os.path.join(root, head, stem.split('.')[0] + '.py'))


def missing_paths(text):
    """The back-quoted words of ``text`` that read as a path of this
    repository and name nothing in it: ``<top-level dir>/...`` from the
    root, ``<package dir>/...`` from ``autodist_tpu/`` (either may end
    in ``module.attribute``), and a bare source file name
    (``session.py``) anywhere in the tree."""
    top, package, names = _tree()
    missing = set()
    for span in _QUOTED.findall(text):
        for word in span.strip('`').split():
            word = _SUFFIX.sub('', word.strip('"\'(),;[]')).rstrip('.:')
            if not re.fullmatch(r'[\w.\-/]+', word) or word[0] == '/':
                continue
            first, _, rest = word.partition('/')
            if not rest:
                found = word in names or word in READERS_OWN \
                    or not _SOURCE.fullmatch(word)
            elif first in top:
                found = _exists(REPO, word)
            elif first in package:
                found = _exists(PACKAGE, word)
            else:
                continue
            if not found:
                missing.add(word)
    return sorted(missing)


def test_scanner_finds_what_is_gone():
    text = ('`tools/analyze.py:50-52`, `runtime/session.py` and '
            '`utils/jax_env.setup_compile_cache` are here, '
            '`python no_such_file.py` and `tools/no_such_tool.py` are '
            'not; `autodist/runner.py` is another repository\'s.\n'
            '```\npython tests/no_such_test.py::test_x\n```\n')
    assert missing_paths(text) == ['no_such_file.py',
                                   'tests/no_such_test.py',
                                   'tools/no_such_tool.py']


@pytest.mark.parametrize('document', DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        assert missing_paths(f.read()) == []
