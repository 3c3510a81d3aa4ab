"""Of the requests at or above the 95th percentile of TTFT by the
program's own request ledger (submitted since the window opened; its
TTFT counts from submit, not from the due instant), the share of their
TTFT sub-book that lies in the named phases, in percent."""
from benchmarks import spans as S
from benchmarks import window


def read(ctx, phases):
    got = S.window_spans(ctx)
    if got is None:
        return None
    _, t_lo = got
    from paddle_tpu.observability import reqledger
    records = getattr(reqledger.get_ledger(), 'window_records', None)
    if records is None:
        return None
    recs = [r for r in records() if r['ts'] >= t_lo
            and r['ttft_s'] is not None]
    if not recs:
        return None
    cut = window.percentile([r['ttft_s'] for r in recs], 95)
    tail = [r for r in recs if r['ttft_s'] >= cut]
    part = sum(r['ttft_phases'].get(p, 0.0) for r in tail for p in phases)
    return 100.0 * part / sum(r['ttft_s'] for r in tail)
