"""Seconds of set-up spent making programs: every ``pb.compile.*`` span
(lower + compile, and the first call of each program, which loads or
builds its executable)."""


def reduce(trace, spans, ctx):
    total = sum(e - s for name, s, e in spans.records
                if name.startswith("pb.compile."))
    return total if total > 0 else None
