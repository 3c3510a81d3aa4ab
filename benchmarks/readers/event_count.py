"""How many events of one name the program emitted since the window
opened (`benchmarks/spans.py`'s `t_lo`: the start of the oldest router
step the window counts). A program that does not declare the event (the
parent of the PR that brought it) could not have counted: that reads
None, not 0, like a log that has dropped part of the range."""
from benchmarks import spans as S


def read(ctx, event):
    got = S.window_spans(ctx)
    if got is None:
        return None
    from paddle_tpu import observability as obs
    if event not in obs.EVENT_SCHEMA:
        return None
    return float(sum(1 for e in obs.get_event_log().events()
                     if e['name'] == event and e.get('ph') == 'i'
                     and e['ts'] >= got[1]))
