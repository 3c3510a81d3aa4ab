"""Parameters and the bytes of a decode sub-step of a MiMo-V2
configuration (`configs/mimo-v2.5.json`), computed from shapes: two
kinds of attention layer with KV head counts and cache rows of their
own, K wider than V, and expert layers of which `n_routed_experts` are
HELD here out of the `expert_share.routed` the router scores.
`counts_afmoe.py` and `counts_lfm2.py` count one KV row size and every
expert; nothing here reads the program."""
from __future__ import annotations

from benchmarks.counts import dtype_bytes

WINDOW = 1


def is_window(cfg, i):
    return cfg['hybrid_layer_pattern'][i] == WINDOW


def kv_heads(cfg, i):
    return cfg['swa_num_key_value_heads'] if is_window(cfg, i) \
        else cfg['num_key_value_heads']


def has_sink(cfg, i):
    return bool(cfg['add_swa_attention_sink_bias'] if is_window(cfg, i)
                else cfg['add_full_attention_sink_bias'])


def attention_params(cfg, i):
    """q is hidden x (heads x 192), k hidden x (KV heads x 192), v hidden
    x (KV heads x 128), o (heads x 128) x hidden, and a sink a query
    head where the layer has one; no biases."""
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    hd, vd, nkv = cfg['head_dim'], cfg['v_head_dim'], kv_heads(cfg, i)
    return (h * nh * hd + h * nkv * hd + h * nkv * vd + nh * vd * h
            + (nh if has_sink(cfg, i) else 0))


def norm_params(cfg):
    """Two RMSNorms over the hidden size a layer."""
    return 2 * cfg['hidden_size']


def expert_params(cfg):
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def router_params(cfg):
    """The router's matrix over every expert it scores, held or not,
    and the selection bias."""
    routed = cfg['expert_share']['routed']
    return cfg['hidden_size'] * routed + routed


def dense_mlp_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def expert_layers(cfg):
    return sum(cfg['moe_layer_freq'][:cfg['num_hidden_layers']])


def layer_params(cfg, i, experts=None):
    """`experts`: how many routed experts to count (None: those held)."""
    n = attention_params(cfg, i) + norm_params(cfg)
    if not cfg['moe_layer_freq'][i]:
        return n + dense_mlp_params(cfg)
    held = cfg['n_routed_experts'] if experts is None else experts
    return n + held * expert_params(cfg) + router_params(cfg)


def total_params(cfg, experts=None):
    """Every parameter held here, as the configuration file's `params`
    states: the layers, the final norm, the embedding and the untied
    head. `experts` = `num_experts_per_tok` counts what one token
    activates."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, i, experts)
                for i in range(cfg['num_hidden_layers']))
            + h + 2 * cfg['vocab_size'] * h)


def always_read_params(cfg):
    """What every decode sub-step must read whatever the router says:
    all of every layer but its routed experts, the final norm and the
    head's slice. The embedding is a gather of a row a slot and is left
    out."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, i, 0)
                for i in range(cfg['num_hidden_layers']))
            + h + cfg['vocab_size'] * h)


def kv_row_bytes(cfg, window):
    """K and V of ONE position in ONE layer of the kind, in the cache's
    dtype: K `head_dim` wide, V `v_head_dim`."""
    nkv = cfg['swa_num_key_value_heads'] if window \
        else cfg['num_key_value_heads']
    return (nkv * (cfg['head_dim'] + cfg['v_head_dim'])
            * dtype_bytes(cfg['kv_dtype']))


def slot_bytes(cfg, max_length):
    """One slot of the pool: `max_length` rows on every full layer, a
    ring of `min(sliding_window, max_length)` rows on every window
    layer."""
    ring = min(cfg['sliding_window'], max_length)
    return sum(ring * kv_row_bytes(cfg, True) if is_window(cfg, i)
               else max_length * kv_row_bytes(cfg, False)
               for i in range(cfg['num_hidden_layers']))


def decode_substep_bytes(cfg, experts_touched_per_layer, needed_rows_full,
                         needed_rows_window):
    """The least bytes one decode sub-step moves: every non-expert
    weight and the head's slice once, the HELD experts the router
    touched (a mean per expert layer and sub-step, as the program's
    counter gives it), and the cache rows attention needs, each at its
    kind's own row bytes (`needed_rows_full`: summed over slots and full
    layers; `needed_rows_window`: over slots and window layers, a
    slot's rows capped at the window). Rows and experts the program
    reads beyond these are not needed bytes, so the time for these
    bytes is a true lower bound."""
    weights = always_read_params(cfg) + (
        expert_layers(cfg) * float(experts_touched_per_layer)
        * expert_params(cfg))
    return (weights * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows_full) * kv_row_bytes(cfg, False)
            + float(needed_rows_window) * kv_row_bytes(cfg, True))
