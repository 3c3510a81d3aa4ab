"""The decode sub-step of a model with expert layers, attention layers
and conv layers against its memory roofline: the least time for the
bytes the sub-step needs (`counts_lfm2.decode_substep_bytes`: every
non-expert weight and the tied head once, the experts the program's own
counter says the active slots routed to, the K and V rows its attention
layers need, the conv state its active slots read and write) over the
device time of one traced sub-step. Reads `experts_touched`,
`expert_layer_substeps`, `needed_rows` and `state_bytes` off the
`serving.decode_round` spans since the window opened; a program without
them (no state that is not K and V, or the parent of the PR that added
the counts) gives nothing."""
from benchmarks import counts_lfm2
from benchmarks import spans as S
from benchmarks import xtrace

NEEDS = ('experts_touched', 'expert_layer_substeps', 'needed_rows',
         'state_bytes')


def read(ctx, match):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = xtrace.module_time(ctx.trace, match)
    got = S.window_spans(ctx)
    if not n or got is None:
        return None
    rounds = [e['attrs'] for e in S.named(got[0], 'serving.decode_round')
              if all(k in (e.get('attrs') or {}) for k in NEEDS)]
    substeps = sum(a['expert_layer_substeps'] for a in rounds)
    if not substeps:
        return None
    block = ctx.raw['decode_block']
    touched = sum(a['experts_touched'] for a in rounds) / substeps
    rows = sum(a['needed_rows'] for a in rounds) / len(rounds)
    # the span's bytes are a round's: `decode_block` sub-steps
    state = sum(a['state_bytes'] for a in rounds) / len(rounds) / block
    need = counts_lfm2.decode_substep_bytes(ctx.config, touched, rows, state)
    substep_s = t / n / block
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
