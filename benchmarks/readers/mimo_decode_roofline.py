"""The decode sub-step of a model with a cache geometry per layer kind
and a share of its experts held, against its memory roofline: the least
time for the bytes the sub-step needs
(`counts_mimo.decode_substep_bytes`: every non-expert weight and the
head's slice once, the HELD experts the program's own counter says the
active slots routed to, the rows its full layers need and the rows its
window layers' rings need, each at its own row bytes) over the device
time of one traced sub-step. Reads `experts_touched`,
`expert_layer_substeps`, `needed_rows` and `needed_rows_window` off the
`serving.decode_round` spans since the window opened; a program without
them (no ring, or the parent of the PR that added the counts) gives
nothing."""
from benchmarks import counts_mimo
from benchmarks import spans as S
from benchmarks import xtrace

NEEDS = ('experts_touched', 'expert_layer_substeps', 'needed_rows',
         'needed_rows_window')


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
    touched = sum(a['experts_touched'] for a in rounds) / substeps
    window = sum(a['needed_rows_window'] for a in rounds) / len(rounds)
    full = sum(a['needed_rows'] for a in rounds) / len(rounds) - window
    need = counts_mimo.decode_substep_bytes(ctx.config, touched, full,
                                            window)
    substep_s = t / n / ctx.raw['decode_block']
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
