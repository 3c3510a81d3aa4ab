"""The seconds the program spent inside spans of one name (or of several:
a serve cell steps `serving.router_step`, a train cell `train.step`)
BEFORE the window opened: the running sum of
`paddle_span_seconds{name=span}`, which does not drop, less the
durations of those spans since the window opened (`benchmarks/spans.py`)
— warm-up's steps with their programs' builds inside, and the warm wave
or warm phase. None where the window's spans are incomplete, and where
the program does not account for its set-up at all
(`paddle_setup_seconds_total`: the parent of the PR that brought it) —
this is one term of `setup_s` beside the import and the constructor, and
says nothing without them."""
from benchmarks import spans as S


def read(ctx, span):
    got = S.window_spans(ctx)
    if got is None:
        return None
    from paddle_tpu import observability as obs
    reg = obs.get_registry()
    fam = reg.get('paddle_span_seconds')
    if fam is None or reg.get('paddle_setup_seconds_total') is None:
        return None
    names = {span} if isinstance(span, str) else set(span)
    total = sum(child.sum for key, child in fam.children()
                if key[0] in names)
    inside = sum(e['dur'] for e in got[0] if e['name'] in names)
    return max(total - inside, 0.0)
