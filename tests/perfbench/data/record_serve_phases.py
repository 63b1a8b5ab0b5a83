"""How ``serve_phases_v5e.xplane.pb`` was made (my chip run, PR 24):

  chiprun --chips 1 -- python3 tests/perfbench/data/record_serve_phases.py

One short window of the cell ``mistral7b-serve-steady`` through the
serve runner's own ``run`` on one TPU v5e: 2 s of its traffic (6
requests of the cell's schedule, Mistral-7B widths at depth 16), the
last second traced.  The decode program at these widths is some 9,000
device operations a step, so a second of it is 21 MB: ``cut_xplane``
keeps the chip's plane and the host's, and of their events those of
``STEPS`` engine steps around the stretch's first prefill chunk (the
program's ``bf.engine.*`` spans beside the device's operations and the
benchmark's ``pb.*``; ``pb.trace_window`` goes, so that the window of
the cut file is the extent of its device operations).  The cut trace
goes to ``chiprun_out/serve_phases_v5e.xplane.pb.gz`` with a
description of its planes beside it.  The tests read the copy kept
here.  ``--cut <file.xplane.pb.gz>`` cuts a recording made earlier.
"""

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT]

SEED = 2147494224
SECONDS = 2.0
STEPS = 6
KEEP_PLANES = ("/device:TPU:0", "/host:CPU")


def _put_varint(out: bytearray, value: int) -> None:
    while value >= 0x80:
        out.append((value & 0x7F) | 0x80)
        value >>= 7
    out.append(value)


def _put_field(out: bytearray, number: int, payload) -> None:
    _put_varint(out, (number << 3) | 2)
    _put_varint(out, len(payload))
    out += payload


def cut_xplane(raw: bytes, steps: int = STEPS) -> bytes:
    """The ``XSpace`` in ``raw`` with ``KEEP_PLANES`` only and, of their
    lines' events, those that begin inside ``steps`` consecutive
    ``bf.engine.step`` spans, from two before the first that holds a
    prefill chunk; without ``pb.trace_window``.  xplane.proto: XSpace.planes = 1; XPlane.name =
    2, .lines = 3, .event_metadata = 4; XLine.timestamp_ns = 3, .events
    = 4; XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3."""
    from perfbench.harness import program_trace as pt

    buf = memoryview(raw)

    def planes():
        for f, w, plane in pt._fields(buf, 0, len(buf)):
            if f == 1 and w == 2:
                name = next(pt._text(buf, v) for g, x, v
                            in pt._fields(buf, *plane) if g == 2 and x == 2)
                if name in KEEP_PLANES:
                    yield plane

    def names_of(plane):
        out = {}
        for meta in pt._map_values(buf, plane, 4):
            fields = {g: v for g, _, v in pt._fields(buf, *meta)}
            out[fields[1]] = pt._text(buf, fields[2]) if 2 in fields else ""
        return out

    def events_of(line):
        fields = list(pt._fields(buf, *line))
        t0_ps = 1000 * next((v for g, w, v in fields if g == 3 and w == 0), 0)
        for g, w, v in fields:
            if g == 4 and w == 2:
                ev = {h: x for h, _, x in pt._fields(buf, *v)}
                begin = t0_ps + ev.get(2, 0)
                yield v, ev.get(1), begin, begin + ev.get(3, 0)

    def spans_named(name):
        return sorted(
            (b, e) for plane in planes() for names in [names_of(plane)]
            for f, w, line in pt._fields(buf, *plane) if f == 3 and w == 2
            for _, mid, b, e in events_of(line) if names.get(mid) == name)

    spans = spans_named("bf.engine.step")
    chunk = spans_named("bf.engine.prefill_chunk")[0][0]
    first = max(0, next(i for i, (b, e) in enumerate(spans)
                        if b <= chunk <= e) - 2)
    lo, hi = spans[first][0], spans[first + steps - 1][1]
    out = bytearray()
    for plane in planes():
        names, body = names_of(plane), bytearray()
        for f, w, v in pt._fields(buf, *plane):
            if f == 3 and w == 2:
                line = bytearray()
                kept = {span for span, mid, b, _ in events_of(v)
                        if lo <= b <= hi
                        and names.get(mid) != "pb.trace_window"}
                for g, x, y in pt._fields(buf, *v):
                    if x == 0:
                        _put_varint(line, g << 3)
                        _put_varint(line, y)
                    elif g != 4 or y in kept:
                        _put_field(line, g, buf[y[0]:y[1]])
                _put_field(body, 3, line)
            elif w == 0:
                _put_varint(body, f << 3)
                _put_varint(body, v)
            else:
                _put_field(body, f, buf[v[0]:v[1]])
        _put_field(out, 1, body)
    return bytes(out)


def main() -> int:
    if sys.argv[1:2] == ["--cut"]:
        import gzip

        with gzip.open(sys.argv[2]) as fh:
            return write_cut(fh.read(), os.path.dirname(sys.argv[2]))

    import jax

    from perfbench.harness import clocks, device, loader, trace as tr
    from perfbench.runners import serve

    device.configure_compile_cache()
    cell = loader.load_cell("mistral7b-serve-steady")
    out = os.path.join(ROOT, "chiprun_out")
    log = os.path.join(out, "serve_phases_trace")
    shutil.rmtree(log, ignore_errors=True)
    os.makedirs(log)
    result = serve.run(cell, SEED, SECONDS, True, jax.devices()[:1],
                       clocks.Spans(), clocks.now(), log)
    with open(tr.find_xplane(log), "rb") as fh:
        raw = fh.read()
    print(len(raw), "bytes; correct:", result["correct"])
    shutil.rmtree(log)
    return write_cut(raw, out)


def write_cut(raw: bytes, out: str) -> int:
    import gzip

    from jax.profiler import ProfileData

    from perfbench.harness import trace as tr

    cut = cut_xplane(raw)
    path = os.path.join(out, "serve_phases_v5e.xplane.pb")
    with open(path, "wb") as fh:
        fh.write(cut)
    with open(os.path.join(out, "serve_phases_v5e.txt"), "w") as fh:
        fh.write(tr.describe(ProfileData.from_file(path), 12))
    os.remove(path)
    with gzip.open(path + ".gz", "wb", 9) as fh:
        fh.write(cut)
    print(len(cut), "bytes cut,", os.path.getsize(path + ".gz"), "gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
