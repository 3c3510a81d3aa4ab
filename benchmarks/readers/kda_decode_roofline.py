"""The decode sub-step of a model with KDA layers, a latent-attention
layer and a share of its experts held, against its memory roofline: the
least time for the bytes the sub-step needs
(`counts_ling3.decode_substep_bytes`: every non-expert weight and the
head's slice once, the HELD experts the program's own counter says the
active slots routed to, the latent rows its attention needs at the
program's logical row bytes, the state its active slots read and write
once each) over the device time of one traced sub-step. Reads
`experts_touched`, `expert_layer_substeps`, `needed_rows`,
`state_bytes`, `latent_layers` and `latent_row_bytes` off the
`serving.decode_round` spans since the window opened; a program without
them (no state beside a latent entry, or the parent of the PR that
added the model) gives nothing."""
from benchmarks import counts_ling3
from benchmarks import spans as S
from benchmarks import xtrace

NEEDS = ('experts_touched', 'expert_layer_substeps', 'needed_rows',
         'state_bytes', 'latent_layers', 'latent_row_bytes')


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
    row_bytes = rounds[-1]['latent_row_bytes'] / rounds[-1]['latent_layers']
    need = counts_ling3.decode_substep_bytes(ctx.config, touched, rows,
                                             state, row_bytes)
    substep_s = t / n / block
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
