"""Mean device-idle time between consecutive device programs while the
engine had work (gaps inside a generator sleep are left out): the host's
orchestration per program."""
from chipbench.trace import union_length


def read(ctx):
    red = ctx.trace
    if red is None:
        return None
    sleeps = [(s.start, s.end) for s in red.spans_named("chipbench.sleep")]
    progs = sorted((p.start, p.end) for p in red.programs)
    gaps, end = [], None
    for a, b in progs:
        if end is not None and a > end:
            mid = 0.5 * (a + end)
            if not any(s0 <= mid <= s1 for s0, s1 in sleeps):
                gaps.append(a - end)
        end = b if end is None else max(end, b)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
