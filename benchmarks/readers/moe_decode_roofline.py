"""The decode sub-step of a model with expert layers against its memory
roofline: the least time for the bytes the sub-step needs
(`counts_afmoe.decode_substep_bytes`: every non-expert weight and the
head once, the experts the program's own counter says the active slots
routed to, the cache rows its attention needs) over the device time of
one traced sub-step. Reads `experts_touched`, `expert_layer_substeps` and
`needed_rows` off the `serving.decode_round` spans since the window
opened; a program without them (no expert layer, or the parent of the PR
that added the counts) gives nothing."""
from benchmarks import counts_afmoe
from benchmarks import spans as S
from benchmarks import xtrace


def read(ctx, match):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = xtrace.module_time(ctx.trace, match)
    got = S.window_spans(ctx)
    if not n or got is None:
        return None
    rounds = [e['attrs'] for e in S.named(got[0], 'serving.decode_round')
              if 'experts_touched' in (e.get('attrs') or {})
              and 'needed_rows' in e['attrs']]
    substeps = sum(a['expert_layer_substeps'] for a in rounds)
    if not substeps:
        return None
    touched = sum(a['experts_touched'] for a in rounds) / substeps
    rows = sum(a['needed_rows'] for a in rounds) / len(rounds)
    need = counts_afmoe.decode_substep_bytes(ctx.config, touched, rows)
    substep_s = t / n / ctx.raw['decode_block']
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
