"""Parameters and the bytes of a decode sub-step of an LFM2-MoE
configuration (`configs/lfm2-24b-a2b.json`), computed from shapes.
`counts.py` counts dense blocks and `counts_afmoe.py` AFMoE's keys;
nothing here reads the program."""
from __future__ import annotations

from benchmarks.counts import dtype_bytes, head_dim

CONV = 'conv'


def conv_params(cfg):
    """The in projection hidden x 3 hidden, the out projection hidden x
    hidden, and `conv_L_cache` taps a channel; no biases."""
    h = cfg['hidden_size']
    return 3 * h * h + h * h + h * cfg['conv_L_cache']


def attention_params(cfg):
    """q and o are hidden x (heads x head_dim), k and v hidden x (KV
    heads x head_dim), and the q and k norms over a head; no biases."""
    h, hd = cfg['hidden_size'], head_dim(cfg)
    nq, nkv = cfg['num_attention_heads'] * hd, cfg['num_key_value_heads'] * hd
    return 2 * h * nq + 2 * h * nkv + 2 * hd


def operator_params(cfg, kind):
    return conv_params(cfg) if kind == CONV else attention_params(cfg)


def norm_params(cfg):
    """Two RMSNorms over the hidden size a layer."""
    return 2 * cfg['hidden_size']


def expert_params(cfg):
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def router_params(cfg):
    """The router's matrix and the selection bias."""
    return cfg['hidden_size'] * cfg['num_experts'] + cfg['num_experts']


def dense_mlp_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def expert_layers(cfg):
    return cfg['num_hidden_layers'] - cfg['num_dense_layers']


def layer_params(cfg, i):
    n = operator_params(cfg, cfg['layer_types'][i]) + norm_params(cfg)
    if i < cfg['num_dense_layers']:
        return n + dense_mlp_params(cfg)
    return n + cfg['num_experts'] * expert_params(cfg) + router_params(cfg)


def total_params(cfg):
    """Every parameter, as the configuration file's `params` states: the
    layers, the final norm, and the embedding, which is the head too."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, i)
                for i in range(cfg['num_hidden_layers']))
            + h + cfg['vocab_size'] * h)


def always_read_params(cfg):
    """What every decode sub-step must read whatever the router says:
    all of every layer but its routed experts, the final norm, and the
    embedding once as the head (its gather of a row a slot is left
    out)."""
    h = cfg['hidden_size']
    return (sum(operator_params(cfg, kind) + norm_params(cfg)
                for kind in cfg['layer_types'][:cfg['num_hidden_layers']])
            + cfg['num_dense_layers'] * dense_mlp_params(cfg)
            + expert_layers(cfg) * router_params(cfg)
            + h + cfg['vocab_size'] * h)


def kv_row_bytes_per_layer(cfg):
    """K and V of ONE position in ONE attention layer, in the cache's
    dtype."""
    return (2 * cfg['num_key_value_heads'] * head_dim(cfg)
            * dtype_bytes(cfg['kv_dtype']))


def state_bytes_per_slot(cfg):
    """One slot's conv state over all conv layers: `conv_L_cache` inputs
    of hidden size a layer, float32."""
    n_conv = cfg['layer_types'][:cfg['num_hidden_layers']].count(CONV)
    return n_conv * cfg['conv_L_cache'] * cfg['hidden_size'] * 4


def decode_substep_bytes(cfg, experts_touched_per_layer, needed_rows,
                         state_bytes):
    """The least bytes one decode sub-step moves: every non-expert
    weight and the tied head once, the experts the router touched (a
    mean per expert layer and sub-step, as the program's counter gives
    it), the K and V rows the attention layers need (`needed_rows`:
    summed over slots and attention layers), and the conv state the
    active slots read and write (`state_bytes`: both ways, a sub-step).
    Rows, experts and state the program moves beyond these are not
    needed bytes, so the time for these bytes is a true lower bound."""
    weights = always_read_params(cfg) + (
        expert_layers(cfg) * float(experts_touched_per_layer)
        * expert_params(cfg))
    return (weights * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows) * kv_row_bytes_per_layer(cfg)
            + float(state_bytes))
