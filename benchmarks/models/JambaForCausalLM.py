"""`nlp/jamba.py JambaForCausalLM` from a configuration file. The
canonical leaves are per layer and have the program's own shapes, so
`fill` slices nothing; the head is the embedding, so there is no leaf
for it. Which layers attend is the file's offset and period, as the
program's configuration class and the reference both read them."""
from __future__ import annotations

reference = 'jamba'

_KEYS = ('vocab_size', 'hidden_size', 'intermediate_size',
         'num_hidden_layers', 'num_attention_heads', 'num_key_value_heads',
         'attn_layer_offset', 'attn_layer_period', 'expert_layer_offset',
         'expert_layer_period', 'num_experts', 'num_experts_per_tok',
         'hidden_act', 'mamba_d_state', 'mamba_d_conv', 'mamba_expand',
         'mamba_dt_rank', 'mamba_conv_bias', 'mamba_proj_bias',
         'use_mamba_kernels', 'sliding_window', 'num_logits_to_keep',
         'max_position_embeddings', 'rms_norm_eps', 'tie_word_embeddings')


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.jamba import JambaConfig, JambaForCausalLM
    conf = JambaConfig(**{k: cfg[k] for k in _KEYS}, **extra)
    with paddle.LazyGuard():
        return JambaForCausalLM(conf)


def name_map(cfg):
    from benchmarks.reference.jamba import FULL, layer_types
    out = {'model.embed_tokens.weight': ('embed', None),
           'model.final_layernorm.weight': ('norm', None)}
    shared = {'input_layernorm.weight': 'in_norm',
              'pre_ff_layernorm.weight': 'ff_norm',
              'feed_forward.gate_proj.weight': 'mlp_gate',
              'feed_forward.up_proj.weight': 'mlp_up',
              'feed_forward.down_proj.weight': 'mlp_down'}
    attn = {'self_attn.q_proj.weight': 'q_w',
            'self_attn.k_proj.weight': 'k_w',
            'self_attn.v_proj.weight': 'v_w',
            'self_attn.o_proj.weight': 'o_w'}
    mamba = {'mamba.in_proj.weight': 'in_w',
             'mamba.conv_weight': 'conv_w', 'mamba.conv_bias': 'conv_b',
             'mamba.x_proj.weight': 'x_w',
             'mamba.dt_layernorm.weight': 'dt_norm',
             'mamba.b_layernorm.weight': 'b_norm',
             'mamba.c_layernorm.weight': 'c_norm',
             'mamba.dt_proj.weight': 'dt_w', 'mamba.dt_proj.bias': 'dt_b',
             'mamba.A_log': 'a_log', 'mamba.D': 'd',
             'mamba.out_proj.weight': 'out_w'}
    for i, kind in enumerate(layer_types(cfg)):
        for prog, canon in {**shared,
                            **(attn if kind == FULL else mamba)}.items():
            out[f'model.layers.{i}.{prog}'] = (f'l{i}.{canon}', None)
    return out
