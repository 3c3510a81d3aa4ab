"""Parameters and the bytes of a decode sub-step of a Ling-3.0
configuration (`configs/ling-3.0-flash.json`), computed from shapes: KDA
layers whose past is a matrix state a head and the convolutions' last
inputs, one latent-attention layer in `layer_group_size` whose output is
gated by head, and expert layers of which `num_experts` are HELD here
out of the `expert_share.routed` the router scores, beside one shared
expert. `counts_lfm2.py` counts a state of one leaf and every expert,
`counts_dsv3.py` latent rows on every layer; nothing here reads the
program."""
from __future__ import annotations

from benchmarks.counts import dtype_bytes

MLA = 'mla'


def is_latent(cfg, i):
    return cfg['layer_types'][i] == MLA


def is_expert_layer(cfg, i):
    return i >= cfg['first_k_dense_replace']


def published_layer_types(cfg, layers):
    """The family's rule for `layer_group_size`: layer i attends iff
    `(i + 1) % layer_group_size == 0`."""
    return [MLA if (i + 1) % cfg['layer_group_size'] == 0 else 'kda'
            for i in range(layers)]


def kda_params(cfg):
    """Six hidden x (heads x head) products (q, k, v, the decay's gate,
    the output's gate, out), three filters of `short_conv_kernel_size`
    taps a channel, the update's strength hidden x heads, `A_log` a
    head, `dt_bias` a channel, the output norm over a head."""
    h, nh, d = cfg['hidden_size'], cfg['num_attention_heads'], cfg['head_dim']
    return (6 * h * nh * d + 3 * nh * d * cfg['short_conv_kernel_size']
            + h * nh + nh + nh * d + d)


def latent_attention_params(cfg):
    """q hidden x (heads x (nope + rope)); kv_a hidden x (latent +
    rope); the latent norm; kv_b latent x (heads x (nope + v)); the gate
    hidden x heads; o (heads x v) x hidden."""
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    lat, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    rd, vd = cfg['qk_rope_head_dim'], cfg['v_head_dim']
    return (h * nh * (nope + rd) + h * (lat + rd) + lat
            + lat * nh * (nope + vd) + h * nh + nh * vd * h)


def mixer_params(cfg, kind):
    return latent_attention_params(cfg) if kind == MLA else kda_params(cfg)


def norm_params(cfg):
    """Two RMSNorms over the hidden size a layer."""
    return 2 * cfg['hidden_size']


def expert_params(cfg):
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def shared_params(cfg):
    return cfg['num_shared_experts'] * expert_params(cfg)


def router_params(cfg):
    """The router's matrix over every expert it scores, held or not,
    and the selection bias."""
    routed = cfg['expert_share']['routed']
    return cfg['hidden_size'] * routed + routed


def dense_mlp_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def expert_layers(cfg):
    return sum(is_expert_layer(cfg, i)
               for i in range(cfg['num_hidden_layers']))


def layer_params(cfg, kind, expert_layer, experts=None):
    """`experts`: how many routed experts to count (None: those held)."""
    n = mixer_params(cfg, kind) + norm_params(cfg)
    if not expert_layer:
        return n + dense_mlp_params(cfg)
    held = cfg['num_experts'] if experts is None else experts
    return n + held * expert_params(cfg) + shared_params(cfg) \
        + router_params(cfg)


def total_params(cfg, experts=None):
    """Every parameter held here, as the configuration file's `params`
    states: the layers, the final norm, the embedding and the untied
    head. `experts` = `num_experts_per_tok` counts what one token
    activates."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, cfg['layer_types'][i],
                             is_expert_layer(cfg, i), experts)
                for i in range(cfg['num_hidden_layers']))
            + h + 2 * cfg['vocab_size'] * h)


def published_params(cfg, experts=None):
    """The uncut model by the file's `published` counts: every layer by
    the rule, `first_k_dense_replace` dense ones, every expert the
    router scores (or `experts` of them), the whole vocabulary. The
    multi-token-prediction layer is left out, as in `left_out`."""
    pub = cfg['published']
    layers, dense = pub['num_hidden_layers'], pub['first_k_dense_replace']
    e = cfg['expert_share']['routed'] if experts is None else experts
    kinds = published_layer_types(cfg, layers)
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, kinds[i], i >= dense, e)
                for i in range(layers)) + h + 2 * pub['vocab_size'] * h)


def always_read_params(cfg):
    """What every decode sub-step must read whatever the router says:
    all of every layer but its routed experts, the final norm and the
    head's slice. The embedding is a gather of a row a slot and is left
    out."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, cfg['layer_types'][i],
                             is_expert_layer(cfg, i), 0)
                for i in range(cfg['num_hidden_layers']))
            + h + cfg['vocab_size'] * h)


def latent_row_bytes(cfg):
    """The cache of ONE position in ONE latent layer, in the cache's
    dtype: `kv_lora_rank + qk_rope_head_dim` numbers — the LOGICAL row,
    whatever lanes the device pads."""
    return ((cfg['kv_lora_rank'] + cfg['qk_rope_head_dim'])
            * dtype_bytes(cfg['kv_dtype']))


def state_bytes_per_layer(cfg):
    """One slot's entry of ONE KDA layer, float32: the matrix state,
    heads x head x head, and the last `short_conv_kernel_size - 1`
    inputs of the three convolutions' heads x head channels each."""
    nh, d = cfg['num_attention_heads'], cfg['head_dim']
    return 4 * (nh * d * d + (cfg['short_conv_kernel_size'] - 1) * 3 * nh * d)


def state_bytes_per_slot(cfg):
    kda = sum(not is_latent(cfg, i) for i in range(cfg['num_hidden_layers']))
    return kda * state_bytes_per_layer(cfg)


def slot_bytes(cfg, max_length):
    """One slot of the pool: the state of every KDA layer and
    `max_length` latent rows on every attending layer."""
    latent = sum(is_latent(cfg, i) for i in range(cfg['num_hidden_layers']))
    return state_bytes_per_slot(cfg) \
        + latent * max_length * latent_row_bytes(cfg)


def decode_substep_bytes(cfg, experts_touched_per_layer, needed_rows,
                         state_bytes, row_bytes=None):
    """The least bytes one decode sub-step moves: every non-expert
    weight and the head's slice once, the HELD experts the router
    touched (a mean per expert layer and sub-step, as the program's
    counter gives it), the latent rows attention needs (`needed_rows`:
    summed over slots and latent layers) at `row_bytes` a row a layer —
    `latent_row_bytes(cfg)` unless the program states its own — and the
    state the active slots read and write (`state_bytes`: both ways, a
    sub-step: ONE read and ONE write of every leaf). Rows, experts and
    passes over the state beyond these, and lanes the device pads, are
    not needed bytes, so the time for these bytes is a true lower
    bound."""
    if row_bytes is None:
        row_bytes = latent_row_bytes(cfg)
    weights = always_read_params(cfg) + (
        expert_layers(cfg) * float(experts_touched_per_layer)
        * expert_params(cfg))
    return (weights * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows) * row_bytes + float(state_bytes))
