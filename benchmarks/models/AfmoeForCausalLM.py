"""`nlp/afmoe.py AfmoeForCausalLM` from a configuration file. The
canonical leaves are per layer and have the program's own shapes, so
`fill` slices nothing."""
from __future__ import annotations

reference = 'afmoe'

_KEYS = ('vocab_size', 'hidden_size', 'intermediate_size',
         'moe_intermediate_size', 'num_hidden_layers', 'num_dense_layers',
         'num_attention_heads', 'num_key_value_heads', 'head_dim',
         'num_experts', 'num_experts_per_tok', 'num_shared_experts',
         'route_norm', 'route_scale', 'score_func', 'sliding_window',
         'global_attn_every_n_layers', 'layer_types',
         'max_position_embeddings', 'rms_norm_eps', 'rope_theta',
         'mup_enabled', 'tie_word_embeddings')


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
    kw = {k: cfg[k] for k in _KEYS}
    # `aot.py --layers N` cuts the depth for a quick look
    kw['layer_types'] = kw['layer_types'][:kw['num_hidden_layers']]
    conf = AfmoeConfig(**kw, **extra)
    with paddle.LazyGuard():
        return AfmoeForCausalLM(conf)


def name_map(cfg):
    out = {'model.embed_tokens.weight': ('embed', None),
           'model.norm.weight': ('norm', None),
           'lm_head.weight': ('head', None)}
    common = {
        'input_layernorm.weight': 'in_norm',
        'self_attn.q_proj.weight': 'q_w', 'self_attn.k_proj.weight': 'k_w',
        'self_attn.v_proj.weight': 'v_w',
        'self_attn.gate_proj.weight': 'g_w',
        'self_attn.o_proj.weight': 'o_w',
        'self_attn.q_norm.weight': 'q_norm',
        'self_attn.k_norm.weight': 'k_norm',
        'post_attention_layernorm.weight': 'post_attn_norm',
        'pre_mlp_layernorm.weight': 'pre_mlp_norm',
        'post_mlp_layernorm.weight': 'post_mlp_norm'}
    dense = {'mlp.gate_proj.weight': 'mlp_gate',
             'mlp.up_proj.weight': 'mlp_up',
             'mlp.down_proj.weight': 'mlp_down'}
    sparse = {'mlp.router.weight': 'router_w',
              'mlp.expert_bias': 'expert_bias',
              'mlp.gate_w': 'experts_gate', 'mlp.up_w': 'experts_up',
              'mlp.down_w': 'experts_down',
              'mlp.shared_experts.gate_proj.weight': 'shared_gate',
              'mlp.shared_experts.up_proj.weight': 'shared_up',
              'mlp.shared_experts.down_proj.weight': 'shared_down'}
    for i in range(cfg['num_hidden_layers']):
        mlp = sparse if i >= cfg['num_dense_layers'] else dense
        for prog, canon in {**common, **mlp}.items():
            out[f'model.layers.{i}.{prog}'] = (f'l{i}.{canon}', None)
    return out
