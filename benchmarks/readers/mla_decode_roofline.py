"""The decode sub-step of a model with latent attention and expert
layers against its memory roofline: the least time for the bytes the
sub-step needs (`counts_dsv3.decode_substep_bytes`: every non-expert
weight and the head once, the experts the program's own counter says
the active slots routed to, the latent rows its attention needs at
their logical row bytes) over the device time of one traced sub-step.
Reads `experts_touched`, `expert_layer_substeps`, `needed_rows`,
`latent_layers` and `latent_row_bytes` off the `serving.decode_round`
spans since the window opened: a row's bytes are the PROGRAM's (its
pool's logical row over its latent layers), so a pool of another
precision is counted as it is; a program without them (no latent
entry, or the parent of the PR that added the counts) gives nothing."""
from benchmarks import counts_dsv3
from benchmarks import spans as S
from benchmarks import xtrace

NEEDS = ('experts_touched', 'expert_layer_substeps', 'needed_rows',
         'latent_layers', 'latent_row_bytes')


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
    rows = sum(a['needed_rows'] for a in rounds) / len(rounds)
    row_bytes = rounds[-1]['latent_row_bytes'] / rounds[-1]['latent_layers']
    need = counts_dsv3.decode_substep_bytes(ctx.config, touched, rows,
                                            row_bytes)
    substep_s = t / n / ctx.raw['decode_block']
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
