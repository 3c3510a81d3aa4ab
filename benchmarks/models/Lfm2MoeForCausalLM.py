"""`nlp/lfm2.py Lfm2MoeForCausalLM` from a configuration file. The
canonical leaves are per layer and have the program's own shapes, so
`fill` slices nothing; the head is the embedding, so there is no leaf
for it."""
from __future__ import annotations

reference = 'lfm2'

_KEYS = ('vocab_size', 'hidden_size', 'intermediate_size',
         'moe_intermediate_size', 'num_hidden_layers', 'num_dense_layers',
         'num_attention_heads', 'num_key_value_heads', 'num_experts',
         'num_experts_per_tok', 'norm_topk_prob', 'routed_scaling_factor',
         'use_expert_bias', 'conv_L_cache', 'conv_bias', 'layer_types',
         'max_position_embeddings', 'norm_eps')


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
    kw = {k: cfg[k] for k in _KEYS}
    # `aot.py --layers N` cuts the depth for a quick look
    kw['layer_types'] = kw['layer_types'][:kw['num_hidden_layers']]
    conf = Lfm2MoeConfig(rope_theta=cfg['rope_parameters']['rope_theta'],
                         **kw, **extra)
    with paddle.LazyGuard():
        return Lfm2MoeForCausalLM(conf)


def name_map(cfg):
    out = {'model.embed_tokens.weight': ('embed', None),
           'model.embedding_norm.weight': ('norm', None)}
    norms = {'operator_norm.weight': 'op_norm',
             'ffn_norm.weight': 'ffn_norm'}
    conv = {'conv.in_proj.weight': 'in_w', 'conv.conv_weight': 'conv_w',
            'conv.out_proj.weight': 'out_w'}
    attn = {'self_attn.q_proj.weight': 'q_w',
            'self_attn.k_proj.weight': 'k_w',
            'self_attn.v_proj.weight': 'v_w',
            'self_attn.o_proj.weight': 'o_w',
            'self_attn.q_norm.weight': 'q_norm',
            'self_attn.k_norm.weight': 'k_norm'}
    dense = {'feed_forward.gate_proj.weight': 'mlp_gate',
             'feed_forward.up_proj.weight': 'mlp_up',
             'feed_forward.down_proj.weight': 'mlp_down'}
    sparse = {'feed_forward.router.weight': 'router_w',
              'feed_forward.expert_bias': 'expert_bias',
              'feed_forward.gate_w': 'experts_gate',
              'feed_forward.up_w': 'experts_up',
              'feed_forward.down_w': 'experts_down'}
    layer_types = cfg['layer_types'][:cfg['num_hidden_layers']]
    for i, kind in enumerate(layer_types):
        op = conv if kind == 'conv' else attn
        mlp = sparse if i >= cfg['num_dense_layers'] else dense
        for prog, canon in {**norms, **op, **mlp}.items():
            out[f'model.layers.{i}.{prog}'] = (f'l{i}.{canon}', None)
    return out
