"""A counter or gauge family of the program's own registry
(`paddle_tpu.observability.get_registry()`), as the sum of its children
whose labels match `labels` (all of them without). What set-up cost is
read this way: its spans have left the bounded event ring long before a
reader runs, and a counter does not drop. A program that does not
declare the family (the parent of the PR that brought it) could not
have counted: that reads None, not 0."""


def read(ctx, metric, labels=None):
    try:
        from paddle_tpu import observability as obs
    except ImportError:
        return None
    fam = obs.get_registry().get(metric)
    if fam is None or fam.type not in ('counter', 'gauge'):
        return None
    labels = labels or {}
    if not set(labels) <= set(fam.labelnames):
        return None
    want = [(fam.labelnames.index(k), str(v)) for k, v in labels.items()]
    return float(sum(child.value for key, child in fam.children()
                     if all(key[i] == v for i, v in want)))
