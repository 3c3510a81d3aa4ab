"""Parameters and the bytes of a decode sub-step of a Xing4.0
configuration (`configs/xing4.0-29b-a4b.json`), computed from shapes: a
DeepSeek-V3-style layer (`counts_dsv3.py`: latent rows, a dense first
layer, then experts behind a router and a shared expert) whose query is
COMPRESSED (`q_lora_rank`) and whose residual path is `hc_mult` streams
mixed by a hyper-connection around each of a layer's two blocks. Nothing
here reads the program."""
from __future__ import annotations

from benchmarks import counts_dsv3 as D
from benchmarks.counts import dtype_bytes

is_expert_layer = D.is_expert_layer
expert_layers = D.expert_layers
expert_params = D.expert_params
shared_params = D.shared_params
router_params = D.router_params
dense_mlp_params = D.dense_mlp_params
norm_params = D.norm_params
latent_row_bytes = D.latent_row_bytes
slot_bytes = D.slot_bytes

SUBLAYERS = 2       # attention and the MLP: a hyper-connection each


def attention_params(cfg):
    """q_a hidden x rank, its norm, q_b rank x (heads x (nope + rope));
    kv_a hidden x (latent + rope); the latent norm; kv_b latent x (heads
    x (nope + v)); o (heads x v) x hidden; no biases."""
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    ql, lat = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rd, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                    cfg['v_head_dim'])
    return (h * ql + ql + ql * nh * (nope + rd) + h * (lat + rd) + lat
            + lat * nh * (nope + vd) + nh * vd * h)


def hyper_connection_params(cfg):
    """One sublayer's: `Phi` (n x hidden) x (n + n + n x n), the three
    biases of n, n and n x n, the three scalars."""
    n = cfg['hc_mult']
    maps = 2 * n + n * n
    return n * cfg['hidden_size'] * maps + maps + 3


def layer_params(cfg, i, experts=None):
    """`experts`: how many routed experts to count (None: all)."""
    n = (attention_params(cfg) + norm_params(cfg)
         + SUBLAYERS * hyper_connection_params(cfg))
    if not is_expert_layer(cfg, i):
        return n + dense_mlp_params(cfg)
    e = cfg['n_routed_experts'] if experts is None else experts
    return n + e * expert_params(cfg) + shared_params(cfg) \
        + router_params(cfg)


def total_params(cfg, experts=None, layers=None, dense=None, vocab=None):
    """Every parameter, as the configuration file's `params` states: the
    layers, the final norm, the embedding and the untied head, without
    the multi-token-prediction layer. `experts` = `num_experts_per_tok`
    counts what one token activates; `layers`, `dense`, `vocab` another
    depth, number of leading dense layers and vocabulary than the file's
    (the published ones)."""
    cfg = dict(cfg)
    if layers is not None:
        cfg['num_hidden_layers'] = layers
    if dense is not None:
        cfg['first_k_dense_replace'] = dense
    if vocab is not None:
        cfg['vocab_size'] = vocab
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, i, experts)
                for i in range(cfg['num_hidden_layers']))
            + h + 2 * cfg['vocab_size'] * h)


def always_read_params(cfg):
    """What every decode sub-step must read whatever the router says:
    all of every layer but its routed experts (the hyper-connections'
    `Phi` among it), the final norm and the head's slice. The embedding
    is a gather of a row a slot and is left out."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, i, 0)
                for i in range(cfg['num_hidden_layers']))
            + h + cfg['vocab_size'] * h)


def stream_bytes(cfg, rows, streams=None):
    """The residual path of one sub-step: each sublayer reads the `rows`
    tokens' streams once and writes them once, float32."""
    n = cfg['hc_mult'] if streams is None else streams
    return (cfg['num_hidden_layers'] * SUBLAYERS * 2 * float(rows) * n
            * cfg['hidden_size'] * 4)


def decode_substep_bytes(cfg, experts_touched_per_layer, needed_rows,
                         row_bytes=None, active=0, streams=None):
    """The least bytes one decode sub-step moves: every non-expert
    weight and the head's slice once, the experts the router touched (a
    mean per expert layer and sub-step, as the program's counter gives
    it), the latent rows attention needs (`needed_rows`: summed over
    slots and layers) at `row_bytes` a row a layer —
    `latent_row_bytes(cfg)` unless the program states its own — and the
    `streams` (the program's, else `hc_mult`) of the `active` slots read
    and written once a sublayer. Rows and experts the program reads
    beyond these, lanes the device pads and every further pass over the
    streams are not needed bytes, so the time for these bytes is a true
    lower bound."""
    if row_bytes is None:
        row_bytes = latent_row_bytes(cfg)
    weights = always_read_params(cfg) + (
        expert_layers(cfg) * float(experts_touched_per_layer)
        * expert_params(cfg))
    return (weights * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows) * row_bytes
            + stream_bytes(cfg, active, streams))
