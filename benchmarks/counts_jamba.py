"""Parameters and the bytes of a decode sub-step of a Jamba
configuration (`configs/jamba2-3b.json`), computed from shapes: Mamba-1
layers whose past is a diagonal state and the convolution's last inputs,
multi-query attention on the layers the file's offset and period name, a
dense SwiGLU on every layer, the head tied to the embedding.
`counts_lfm2.py` counts a state of one leaf beside experts,
`counts_ling3.py` a matrix state beside latent rows; nothing here reads
the program."""
from __future__ import annotations

from benchmarks.counts import dtype_bytes, head_dim

FULL = 'full_attention'


def layer_types(cfg):
    """The family's rule: layer i attends iff `i % attn_layer_period ==
    attn_layer_offset`."""
    return [FULL if i % cfg['attn_layer_period'] == cfg['attn_layer_offset']
            else 'mamba' for i in range(cfg['num_hidden_layers'])]


def mamba_layers(cfg):
    return layer_types(cfg).count('mamba')


def attention_layers(cfg):
    return layer_types(cfg).count(FULL)


def d_inner(cfg):
    return cfg['mamba_expand'] * cfg['hidden_size']


def mixer_params(cfg):
    """in hidden x 2 d_inner; `mamba_d_conv` taps and a bias a channel;
    x d_inner x (dt_rank + 2 d_state); the three inner norms; dt dt_rank
    x d_inner and its bias; `A_log` d_inner x d_state; `D`; out d_inner
    x hidden."""
    h, di = cfg['hidden_size'], d_inner(cfg)
    n, r = cfg['mamba_d_state'], cfg['mamba_dt_rank']
    return (h * 2 * di + di * cfg['mamba_d_conv'] + di
            + di * (r + 2 * n) + (r + 2 * n)
            + r * di + di + di * n + di + di * h)


def attention_params(cfg):
    """q and o are hidden x (heads x head_dim), k and v hidden x (KV
    heads x head_dim); no biases, no norms."""
    h, hd = cfg['hidden_size'], head_dim(cfg)
    return (2 * h * cfg['num_attention_heads'] * hd
            + 2 * h * cfg['num_key_value_heads'] * hd)


def mlp_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def norm_params(cfg):
    """Two RMSNorms over the hidden size a layer."""
    return 2 * cfg['hidden_size']


def layer_params(cfg, kind):
    mixer = attention_params(cfg) if kind == FULL else mixer_params(cfg)
    return mixer + mlp_params(cfg) + norm_params(cfg)


def total_params(cfg):
    """Every parameter, as the configuration file's `params` states: the
    layers, the final norm, and the embedding, which is the head too."""
    h = cfg['hidden_size']
    return (sum(layer_params(cfg, kind) for kind in layer_types(cfg))
            + h + cfg['vocab_size'] * h)


def kv_row_bytes_per_layer(cfg):
    """K and V of ONE position in ONE attention layer, in the cache's
    dtype."""
    return (2 * cfg['num_key_value_heads'] * head_dim(cfg)
            * dtype_bytes(cfg['kv_dtype']))


def state_bytes_per_layer(cfg):
    """One slot's entry of ONE Mamba layer, float32: the state, d_inner
    x d_state, and the convolution's last `mamba_d_conv - 1` inputs of
    d_inner channels."""
    return 4 * d_inner(cfg) * (cfg['mamba_d_state']
                               + cfg['mamba_d_conv'] - 1)


def state_bytes_per_slot(cfg):
    return mamba_layers(cfg) * state_bytes_per_layer(cfg)


def slot_bytes(cfg, max_length):
    """One slot of the pool: the state of every Mamba layer and
    `max_length` rows of K and V on every attending layer."""
    return state_bytes_per_slot(cfg) + (
        attention_layers(cfg) * max_length * kv_row_bytes_per_layer(cfg))


def decode_substep_bytes(cfg, needed_rows, state_bytes):
    """The least bytes one decode sub-step moves: every weight once —
    the tied table once, as the head; its gather of a row a slot is left
    out —, the K and V rows the attention layers need (`needed_rows`:
    summed over slots and attention layers) and the state the active
    slots read and write (`state_bytes`: both ways, a sub-step: ONE read
    and ONE write of both leaves). Rows and passes over the state beyond
    these, lanes the device pads, and the MXU's three passes over the
    128-row products are not needed bytes, so the time for these bytes
    is a true lower bound."""
    return (total_params(cfg) * dtype_bytes(cfg['param_dtype'])
            + float(needed_rows) * kv_row_bytes_per_layer(cfg)
            + float(state_bytes))
