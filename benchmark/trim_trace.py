"""Cut a recorded ``.xplane.pb`` down to a fixture for the tests: the
``XLA Ops`` line of every chip and the benchmark's host spans, for the
last ``steps`` traced steps, as a gzipped text-form XSpace that
``trace_reduce.load_file`` reads back.

    python3 benchmark/trim_trace.py <in.xplane.pb> <out.textproto.gz> [steps]

Event names are cut to ``NAME_LIMIT`` characters: enough for the head,
the result shape and the operation.
"""
import gzip
import sys

NAME_LIMIT = 160


def text_proto(planes):
    """An XSpace in text form from ``{plane: {line: [(name, start_ns,
    dur_ns)]}}``."""
    out = []
    for p, (plane, lines) in enumerate(planes.items(), 1):
        ids = {}
        out.append('planes { id: %d name: "%s"' % (p, plane))
        for l, (line, events) in enumerate(lines.items(), 1):
            out.append('lines { id: %d name: "%s"' % (l, line))
            for name, start, dur in events:
                name = name.replace('\\', '\\\\').replace('"', '\\"')
                i = ids.setdefault(name, len(ids) + 1)
                out.append('events { metadata_id: %d offset_ps: %d '
                           'duration_ps: %d }'
                           % (i, round(start * 1000), round(dur * 1000)))
            out.append('}')
        for name, i in ids.items():
            out.append('event_metadata { key: %d value { id: %d name: "%s" '
                       '} }' % (i, i, name))
        out.append('}')
    return '\n'.join(out)


def trimmed(profile, steps):
    """``text_proto`` of the last ``steps`` traced steps of ``profile``,
    times counted from the first of them."""
    from benchmark import trace_reduce as tr
    trace = tr.load(profile)
    spans = [s for s in trace.spans if s.name == tr.STEP_SPAN][-steps:]
    lo, hi = spans[0].start, max(s.end for s in spans)

    def cut(events):
        return [(e.name[:NAME_LIMIT], e.start - lo, e.dur)
                for e in events if e.end > lo and e.start < hi]
    planes = {'/device:TPU:%d' % chip: {tr.OPS_LINE: cut(ops)}
              for chip, ops in sorted(trace.ops.items())}
    planes[tr.HOST_PLANE] = {'python3': cut(trace.spans)}
    return text_proto(planes)


if __name__ == '__main__':
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from jax.profiler import ProfileData
    text = trimmed(ProfileData.from_file(sys.argv[1]),
                   int(sys.argv[3]) if len(sys.argv) > 3 else 1)
    with gzip.open(sys.argv[2], 'wt', compresslevel=9) as f:
        f.write(text)
