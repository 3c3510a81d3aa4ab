"""`nlp/mimo_v2.py MiMoV2ForCausalLM` from a configuration file. The
canonical leaves are per layer and have the program's own shapes (q, k
and v three leaves, the experts HELD stacked [held, h, f]), so `fill`
slices nothing. The file's `n_routed_experts` counts the experts held
here; `expert_share` gives the router's width and the first of them."""
from __future__ import annotations

reference = 'mimo_v2'

_KEYS = ('vocab_size', 'hidden_size', 'intermediate_size',
         'moe_intermediate_size', 'num_hidden_layers',
         'hybrid_layer_pattern', 'moe_layer_freq', 'num_attention_heads',
         'num_key_value_heads', 'swa_num_key_value_heads', 'head_dim',
         'v_head_dim', 'partial_rotary_factor', 'rope_theta',
         'swa_rope_theta', 'sliding_window', 'attention_value_scale',
         'add_swa_attention_sink_bias', 'add_full_attention_sink_bias',
         'n_routed_experts', 'num_experts_per_tok', 'norm_topk_prob',
         'routed_scaling_factor', 'n_shared_experts', 'scoring_func',
         'n_group', 'topk_group', 'layernorm_epsilon',
         'max_position_embeddings', 'tie_word_embeddings')


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
    kw = {k: cfg[k] for k in _KEYS}
    # `aot.py --layers N` cuts the depth for a quick look
    for pattern in ('hybrid_layer_pattern', 'moe_layer_freq'):
        kw[pattern] = kw[pattern][:kw['num_hidden_layers']]
    share = cfg['expert_share']
    conf = MiMoV2Config(num_routed_experts=share['routed'],
                        first_expert=share['first'], **kw, **extra)
    with paddle.LazyGuard():
        return MiMoV2ForCausalLM(conf)


def name_map(cfg):
    from benchmarks.reference.mimo_v2 import has_sink, is_expert_layer
    out = {'model.embed_tokens.weight': ('embed', None),
           'model.norm.weight': ('norm', None),
           'lm_head.weight': ('head', None)}
    common = {'input_layernorm.weight': 'in_norm',
              'post_attention_layernorm.weight': 'post_norm',
              'self_attn.q_proj.weight': 'q_w',
              'self_attn.k_proj.weight': 'k_w',
              'self_attn.v_proj.weight': 'v_w',
              'self_attn.o_proj.weight': 'o_w'}
    dense = {'mlp.gate_proj.weight': 'mlp_gate',
             'mlp.up_proj.weight': 'mlp_up',
             'mlp.down_proj.weight': 'mlp_down'}
    sparse = {'mlp.router.weight': 'router_w',
              'mlp.expert_bias': 'expert_bias',
              'mlp.gate_w': 'experts_gate', 'mlp.up_w': 'experts_up',
              'mlp.down_w': 'experts_down'}
    for i in range(cfg['num_hidden_layers']):
        names = {**common, **(sparse if is_expert_layer(cfg, i) else dense)}
        if has_sink(cfg, i):
            names['self_attn.sink'] = 'sink'
        for prog, canon in names.items():
            out[f'model.layers.{i}.{prog}'] = (f'l{i}.{canon}', None)
    return out
