"""A ratio of the scalar counts the program's spans carry, in percent,
as the mean over the spans of one name since the window opened: `num`
over the product of `den`. A `den` entry is a count on the span, or
`raw:<key>` for a number of the cell the driver noted (`max_length`)."""
from benchmarks import spans as S


def read(ctx, span, num, den):
    got = S.window_spans(ctx)
    if got is None:
        return None
    ratios = []
    for e in S.named(got[0], span):
        attrs = e.get('attrs') or {}
        if num not in attrs:
            continue
        d = 1.0
        for key in den:
            d *= ctx.raw[key[4:]] if key.startswith('raw:') else attrs[key]
        if d > 0:
            ratios.append(attrs[num] / d)
    if not ratios:
        return None
    return 100.0 * sum(ratios) / len(ratios)
