"""The decode sub-step of a model with latent attention, expert layers
and a residual path of several streams against its memory roofline: the
least time for the bytes the sub-step needs
(`counts_xing4.decode_substep_bytes`: every non-expert weight with the
hyper-connections' and the head's slice once, the experts the program's
own counter says the active slots routed to, the latent rows its
attention needs at their logical row bytes, the active slots' streams
read and written once a sublayer) over the device time of one traced
sub-step. Reads `experts_touched`, `expert_layer_substeps`,
`needed_rows`, `latent_layers`, `latent_row_bytes`, `residual_streams`
and `active` off the `serving.decode_round` spans since the window
opened: a row's bytes and the number of streams are the PROGRAM's; a
program without them (no streams, no latent entry, or the parent of the
PR that added the model) gives nothing."""
from benchmarks import counts_xing4
from benchmarks import spans as S
from benchmarks import xtrace

NEEDS = ('experts_touched', 'expert_layer_substeps', 'needed_rows',
         'latent_layers', 'latent_row_bytes', 'residual_streams', 'active')


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
    active = sum(a['active'] for a in rounds) / len(rounds)
    last = rounds[-1]
    need = counts_xing4.decode_substep_bytes(
        ctx.config, touched, rows,
        last['latent_row_bytes'] / last['latent_layers'], active,
        last['residual_streams'])
    substep_s = t / n / ctx.raw['decode_block']
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
