"""Operations and bytes the algorithms need, computed from shapes.

These are the benchmark's own: the program's cost model
(`observability/cost.py`) is not read. Every function takes a
configuration dict as the files under `configs/` hold it.
"""
from __future__ import annotations

_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2, 'float16': 2, 'int8': 1}


def dtype_bytes(name):
    return _DTYPE_BYTES[name]


def head_dim(cfg):
    return cfg.get('head_dim') or cfg['hidden_size'] // cfg['num_attention_heads']


def kv_heads(cfg):
    return cfg.get('num_key_value_heads') or cfg['num_attention_heads']


def mlp_matrices(cfg):
    """Weight matrices of one MLP: 3 for a gated (SwiGLU) block, else 2."""
    return 3 if cfg['hidden_act'] in ('silu', 'swiglu') else 2


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication on the forward
    pass: the blocks' projections and the output head (also when it is
    tied to the embedding); the embedding gather and the position table
    are excluded, norms and biases are not matmuls."""
    h, hd = cfg['hidden_size'], head_dim(cfg)
    nh, nkv = cfg['num_attention_heads'], kv_heads(cfg)
    attn = h * nh * hd * 2 + h * nkv * hd * 2          # q, o, k, v
    mlp = mlp_matrices(cfg) * h * cfg['intermediate_size']
    return cfg['num_hidden_layers'] * (attn + mlp) + h * cfg['vocab_size']


def total_params(cfg):
    """Every parameter, as the configuration file's `params` states."""
    h = cfg['hidden_size']
    n = matmul_params(cfg)
    if cfg.get('tie_word_embeddings'):
        n += 0                                   # the head IS the embedding
    else:
        n += h * cfg['vocab_size']               # separate embedding
    if cfg.get('position_embedding') == 'learned':
        n += cfg['max_position_embeddings'] * h
    norms_per_layer = 2
    norm_size = h * (2 if cfg['norm'] == 'layernorm' else 1)
    n += (cfg['num_hidden_layers'] * norms_per_layer + 1) * norm_size
    if cfg.get('bias'):
        hd, nh, nkv = head_dim(cfg), cfg['num_attention_heads'], kv_heads(cfg)
        per = (nh * hd + 2 * nkv * hd + h            # q, k, v, o
               + cfg['intermediate_size'] * (mlp_matrices(cfg) - 1) + h)
        n += cfg['num_hidden_layers'] * per
    return n


def train_flops_per_token(cfg, seq):
    """Model flops one trained token needs: 3 x forward (forward plus
    twice that backward), recomputation not counted, head included,
    embedding gather excluded. Attention is counted causal — the
    operations the algorithm needs: QK^T and PV over the visible half,
    2 * 2 * (seq / 2) * heads * head_dim per token and layer."""
    attn = (cfg['num_hidden_layers'] * 2 * 2 * (seq / 2.0)
            * cfg['num_attention_heads'] * head_dim(cfg))
    return 3.0 * (2.0 * matmul_params(cfg) + attn)


def mfu_percent(cfg, seq, tokens_per_s_chip, peak_flops):
    return 100.0 * train_flops_per_token(cfg, seq) * tokens_per_s_chip / peak_flops


def flash_train_flops(cfg, batch, seq):
    """Causal attention flops of one training step in the flash kernels,
    all layers: forward 2 matmuls, backward 5 (recomputed scores, dP,
    dV, dQ, dK), each 2 * batch * heads * seq * (seq / 2) * head_dim."""
    one = 2.0 * batch * cfg['num_attention_heads'] * seq * (seq / 2.0) * head_dim(cfg)
    return cfg['num_hidden_layers'] * 7.0 * one


def flash_train_bytes(cfg, batch, seq, act_bytes=2):
    """HBM bytes the flash forward and backward must move, all layers:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv (row statistics are negligible)."""
    q = batch * seq * cfg['num_attention_heads'] * head_dim(cfg) * act_bytes
    kv = batch * seq * kv_heads(cfg) * head_dim(cfg) * act_bytes
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 2 * kv + 2 * kv
    return cfg['num_hidden_layers'] * float(fwd + bwd)


def roofline_percent(flops, nbytes, seconds, peak_flops, peak_bytes_per_s):
    """Least time the chip could take over the time it took, in percent,
    and which bound is the larger ('compute' or 'memory')."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes_per_s
    bound = 'compute' if t_c >= t_m else 'memory'
    return 100.0 * max(t_c, t_m) / seconds, bound


def weight_bytes(cfg):
    return total_params(cfg) * dtype_bytes(cfg['param_dtype'])


def kv_row_bytes(cfg):
    """Bytes of K and V of ONE token position over all layers, in the
    cache's dtype."""
    return (2 * cfg['num_hidden_layers'] * kv_heads(cfg) * head_dim(cfg)
            * dtype_bytes(cfg['kv_dtype']))


def decode_substep_bytes(cfg, real_rows):
    """Bytes one decode sub-step needs: every weight once plus the REAL
    rows of the cache (rows holding a token of a live request), in the
    cache's dtype. Padding rows of a slot are not needed bytes."""
    return weight_bytes(cfg) + kv_row_bytes(cfg) * float(real_rows)
