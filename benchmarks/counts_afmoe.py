"""Parameters and the bytes of a decode sub-step of an AFMoE
configuration (`configs/trinity-mini.json`), computed from shapes.
`counts.py` counts dense blocks only; nothing here reads the program."""
from __future__ import annotations

from benchmarks.counts import dtype_bytes


def attention_params(cfg):
    """q, gate and o are hidden x (heads x head_dim), k and v hidden x
    (KV heads x head_dim); no biases."""
    h, hd = cfg['hidden_size'], cfg['head_dim']
    nq, nkv = cfg['num_attention_heads'] * hd, cfg['num_key_value_heads'] * hd
    return 3 * h * nq + 2 * h * nkv


def norm_params(cfg):
    """Four RMSNorms over the hidden size and the q and k norms over a
    head, a layer."""
    return 4 * cfg['hidden_size'] + 2 * cfg['head_dim']


def expert_params(cfg):
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def shared_params(cfg):
    return cfg['num_shared_experts'] * expert_params(cfg)


def router_params(cfg):
    """The router's matrix and the selection bias."""
    return cfg['hidden_size'] * cfg['num_experts'] + cfg['num_experts']


def dense_mlp_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def expert_layers(cfg):
    return cfg['num_hidden_layers'] - cfg['num_dense_layers']


def layer_params(cfg, expert_layer):
    n = attention_params(cfg) + norm_params(cfg)
    if not expert_layer:
        return n + dense_mlp_params(cfg)
    return (n + cfg['num_experts'] * expert_params(cfg) + shared_params(cfg)
            + router_params(cfg))


def total_params(cfg):
    """Every parameter, as the configuration file's `params` states:
    the layers, the final norm, the embedding and the untied head."""
    h = cfg['hidden_size']
    return (cfg['num_dense_layers'] * layer_params(cfg, False)
            + expert_layers(cfg) * layer_params(cfg, True)
            + h + 2 * cfg['vocab_size'] * h)


def always_read_params(cfg):
    """What every decode sub-step must read whatever the router says:
    all of every layer but its routed experts, the final norm and the
    head. The embedding is a gather of a row a slot and is left out."""
    h = cfg['hidden_size']
    per_layer = attention_params(cfg) + norm_params(cfg)
    return (cfg['num_hidden_layers'] * per_layer
            + cfg['num_dense_layers'] * dense_mlp_params(cfg)
            + expert_layers(cfg) * (shared_params(cfg) + router_params(cfg))
            + h + cfg['vocab_size'] * h)


def kv_row_bytes_per_layer(cfg):
    """K and V of ONE position in ONE layer, in the cache's dtype."""
    return (2 * cfg['num_key_value_heads'] * cfg['head_dim']
            * dtype_bytes(cfg['kv_dtype']))


def decode_substep_bytes(cfg, experts_touched_per_layer, needed_rows):
    """The least bytes one decode sub-step moves: every non-expert
    weight and the head once, the experts the router touched (a mean
    per expert layer and sub-step, as the program's counter gives it),
    and the cache rows attention needs (`needed_rows`: summed over
    slots and layers, a window layer's rows capped at its window).
    Rows and experts the program reads beyond these are not needed
    bytes, so the time for these bytes is a true lower bound."""
    weights = always_read_params(cfg) + (
        expert_layers(cfg) * float(experts_touched_per_layer)
        * expert_params(cfg))
    return (weights * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows) * kv_row_bytes_per_layer(cfg))
