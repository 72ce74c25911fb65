"""Generate the API reference into docs/api/ (markdown, stdlib-only).

Role parity with the reference's Sphinx autodoc pipeline
(`/root/reference/docs/conf.py` + `docs/templates/`): walk the public
package surface, emit one markdown page per module with module/class/
function docstrings and signatures. This image cannot install Sphinx,
so the generator is inspect-based; `docs/conf.py` remains a Sphinx
scaffold for environments that have it (CI, user machines).

Run (CPU is fine — importing models must not touch the TPU):
    JAX_PLATFORMS=cpu python tools/gen_api_docs.py
"""
import importlib
import inspect
import os
import pkgutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import autodist_tpu  # noqa: E402

OUT_DIR = os.path.join(REPO, 'docs', 'api')
SKIP = {'autodist_tpu.native_build'}   # thin build driver; documented inline


def _signature(obj):
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return '(...)'


def _doc(obj, indent=''):
    doc = inspect.getdoc(obj)
    if not doc:
        return ''
    return '\n'.join(indent + ln for ln in doc.splitlines())


def _is_public(name):
    return not name.startswith('_')


def render_module(mod):
    lines = ['# `%s`' % mod.__name__, '']
    if mod.__doc__:
        lines += [inspect.getdoc(mod), '']
    members = vars(mod)
    classes = [(n, o) for n, o in members.items()
               if inspect.isclass(o) and o.__module__ == mod.__name__
               and _is_public(n)]
    functions = [(n, o) for n, o in members.items()
                 if inspect.isfunction(o) and o.__module__ == mod.__name__
                 and _is_public(n)]
    for name, fn in functions:
        lines += ['## `%s%s`' % (name, _signature(fn)), '']
        d = _doc(fn)
        if d:
            lines += [d, '']
    for name, cls in classes:
        lines += ['## class `%s`' % name, '']
        d = _doc(cls)
        if d:
            lines += [d, '']
        for mname, meth in sorted(vars(cls).items()):
            if not _is_public(mname):
                continue
            target = meth
            if isinstance(meth, (staticmethod, classmethod)):
                target = meth.__func__
            elif isinstance(meth, property):
                pd = _doc(meth.fget) if meth.fget else ''
                lines += ['### `%s.%s` *(property)*' % (name, mname), '']
                if pd:
                    lines += [pd, '']
                continue
            if not (inspect.isfunction(target) or inspect.ismethod(target)):
                continue
            lines += ['### `%s.%s%s`' % (name, mname, _signature(target)),
                      '']
            md = _doc(target)
            if md:
                lines += [md, '']
    return '\n'.join(lines) + '\n'


def main():
    global OUT_DIR
    if len(sys.argv) > 1:   # alternate output dir (tests)
        OUT_DIR = sys.argv[1]
    os.makedirs(OUT_DIR, exist_ok=True)
    pages = []
    for info in pkgutil.walk_packages(autodist_tpu.__path__,
                                      prefix='autodist_tpu.'):
        name = info.name
        if name in SKIP or any(p.startswith('_') for p in name.split('.')):
            continue
        try:
            mod = importlib.import_module(name)
        except Exception as e:   # noqa: BLE001 - optional deps may gate
            print('skip %s: %s' % (name, e))
            continue
        page = name.replace('.', '_') + '.md'
        with open(os.path.join(OUT_DIR, page), 'w') as f:
            f.write(render_module(mod))
        pages.append((name, page))
    # the package root page + index
    with open(os.path.join(OUT_DIR, 'autodist_tpu.md'), 'w') as f:
        f.write(render_module(autodist_tpu))
    with open(os.path.join(OUT_DIR, 'index.md'), 'w') as f:
        f.write('# API reference\n\nGenerated by '
                '`python tools/gen_api_docs.py` from the package '
                'docstrings.\n\n- [`autodist_tpu`](autodist_tpu.md)\n')
        for name, page in sorted(pages):
            f.write('- [`%s`](%s)\n' % (name, page))
    print('wrote %d module pages to %s' % (len(pages) + 1, OUT_DIR))


if __name__ == '__main__':
    main()
