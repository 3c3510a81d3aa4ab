"""A time read from the program's own spans since the window opened
(`benchmarks/spans.py`), in milliseconds, as a median: a span's duration
(`duration`), its self time — duration minus the part its child spans
cover — (`self`, optionally only over spans whose count `where` is
positive), or the time from the end of one span to the end of the next
span of another name (`gap`: the end of a round's token fetch to the
end of the next round's dispatch is the time the device has nothing
queued)."""
from benchmarks import spans as S


def read(ctx, span, of='duration', until=None, where=None):
    got = S.window_spans(ctx)
    if got is None:
        return None
    spans, _ = got
    if of == 'gap':
        return S.median_ms(S.gaps(spans, span, until))
    if of == 'self':
        keep = None if where is None else (lambda a: a.get(where, 0) > 0)
        return S.median_ms(S.self_times(spans, span, keep))
    return S.median_ms([e['dur'] for e in S.named(spans, span)])
