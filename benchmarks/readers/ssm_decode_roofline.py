"""The decode sub-step of a model with state-space layers and a few
attention layers against its memory roofline: the least time for the
bytes the sub-step needs (`counts_jamba.decode_substep_bytes`: every
weight once with the tied table once, the K and V rows its attention
layers need, the state its active slots read and write once each) over
the device time of one traced sub-step. Bytes only, and one pass: a
lower bound, so the share cannot pass 100%; what the matrix unit's three
passes over products 128 rows wide cost beside it is a finding
(PERF.md), not a term of the count. Reads `needed_rows`, `state_bytes`,
`state_layers` and `attn_layers` off the `serving.decode_round` spans
since the window opened; a program without them (no state that is not K
and V, or the parent of the PR that added the model) gives nothing."""
from benchmarks import counts_jamba
from benchmarks import spans as S
from benchmarks import xtrace

NEEDS = ('needed_rows', 'state_bytes', 'state_layers', 'attn_layers')


def read(ctx, match):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = xtrace.module_time(ctx.trace, match)
    got = S.window_spans(ctx)
    if not n or got is None:
        return None
    rounds = [e['attrs'] for e in S.named(got[0], 'serving.decode_round')
              if all(k in (e.get('attrs') or {}) for k in NEEDS)]
    if not rounds:
        return None
    block = ctx.raw['decode_block']
    rows = sum(a['needed_rows'] for a in rounds) / len(rounds)
    # the span's bytes are a round's: `decode_block` sub-steps
    state = sum(a['state_bytes'] for a in rounds) / len(rounds) / block
    need = counts_jamba.decode_substep_bytes(ctx.config, rows, state)
    substep_s = t / n / block
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
