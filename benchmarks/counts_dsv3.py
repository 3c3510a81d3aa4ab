"""Parameters and the bytes of a decode sub-step of a DeepSeek-V3-style
configuration (`configs/kanana-2-30b-a3b.json`), computed from shapes:
latent attention, whose cache row is `kv_lora_rank + qk_rope_head_dim`
numbers a token a layer whatever the number of heads, a dense first
layer, then experts behind a router with the shared experts as one MLP.
`counts_afmoe.py`, `counts_lfm2.py` and `counts_mimo.py` count cache
rows by KV head; nothing here reads the program."""
from __future__ import annotations

from benchmarks.counts import dtype_bytes


def is_expert_layer(cfg, i):
    return i >= cfg['first_k_dense_replace'] \
        and i % cfg['moe_layer_freq'] == 0


def attention_params(cfg):
    """q is hidden x (heads x (nope + rope)); kv_a hidden x (latent +
    rope); the latent norm's weight; kv_b latent x (heads x (nope + v));
    o (heads x v) x hidden; no biases, no query compression."""
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    lat, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    rd, vd = cfg['qk_rope_head_dim'], cfg['v_head_dim']
    return (h * nh * (nope + rd) + h * (lat + rd) + lat
            + lat * nh * (nope + vd) + nh * vd * h)


def norm_params(cfg):
    """Two RMSNorms over the hidden size a layer."""
    return 2 * cfg['hidden_size']


def expert_params(cfg):
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def shared_params(cfg):
    """The shared experts: ONE SwiGLU of their summed width."""
    return cfg['n_shared_experts'] * expert_params(cfg)


def router_params(cfg):
    """The router's matrix and the selection bias."""
    e = cfg['n_routed_experts']
    return cfg['hidden_size'] * e + e


def dense_mlp_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def expert_layers(cfg, layers=None):
    n = cfg['num_hidden_layers'] if layers is None else layers
    return sum(is_expert_layer(cfg, i) for i in range(n))


def layer_params(cfg, i, experts=None):
    """`experts`: how many routed experts to count (None: all)."""
    n = attention_params(cfg) + norm_params(cfg)
    if not is_expert_layer(cfg, i):
        return n + dense_mlp_params(cfg)
    e = cfg['n_routed_experts'] if experts is None else experts
    return n + e * expert_params(cfg) + shared_params(cfg) \
        + router_params(cfg)


def total_params(cfg, experts=None, layers=None):
    """Every parameter, as the configuration file's `params` states: the
    layers, the final norm, the embedding and the untied head. `experts`
    = `num_experts_per_tok` counts what one token activates; `layers`
    another depth than the file's (the published one)."""
    h = cfg['hidden_size']
    n = cfg['num_hidden_layers'] if layers is None else layers
    return (sum(layer_params(cfg, i, experts) for i in range(n))
            + h + 2 * cfg['vocab_size'] * h)


def always_read_params(cfg):
    """What every decode sub-step must read whatever the router says:
    all of every layer but its routed experts, the final norm and the
    head. The embedding is a gather of a row a slot and is left out."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, i, 0)
                for i in range(cfg['num_hidden_layers']))
            + h + cfg['vocab_size'] * h)


def latent_row_bytes(cfg):
    """The cache of ONE position in ONE layer, in the cache's dtype: the
    normed latent and the rotated shared key, `kv_lora_rank +
    qk_rope_head_dim` numbers — the LOGICAL row, whatever lanes the
    device pads."""
    return ((cfg['kv_lora_rank'] + cfg['qk_rope_head_dim'])
            * dtype_bytes(cfg['kv_dtype']))


def slot_bytes(cfg, max_length):
    """One slot of the pool: `max_length` latent rows on every layer."""
    return cfg['num_hidden_layers'] * max_length * latent_row_bytes(cfg)


def decode_substep_bytes(cfg, experts_touched_per_layer, needed_rows,
                         row_bytes=None):
    """The least bytes one decode sub-step moves: every non-expert
    weight and the head once, the experts the router touched (a mean per
    expert layer and sub-step, as the program's counter gives it), and
    the latent rows attention needs (`needed_rows`: summed over slots
    and layers) at `row_bytes` a row a layer — `latent_row_bytes(cfg)`
    unless the program states its own. Rows and experts the program
    reads beyond these, and lanes the device pads, are not needed bytes,
    so the time for these bytes is a true lower bound."""
    if row_bytes is None:
        row_bytes = latent_row_bytes(cfg)
    weights = always_read_params(cfg) + (
        expert_layers(cfg) * float(experts_touched_per_layer)
        * expert_params(cfg))
    return (weights * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows) * row_bytes)
