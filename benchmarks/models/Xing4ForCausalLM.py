"""`nlp/xing4.py Xing4ForCausalLM` from a configuration file. The
canonical leaves are per layer and have the program's own shapes (the
experts stacked [E, h, f]; a sublayer's hyper-connection seven leaves),
so `fill` slices nothing."""
from __future__ import annotations

# at import, before a weight is made: a checkout whose program lacks the
# model (the parent of the PR that added it) fails here, at once
import paddle_tpu.nlp.xing4  # noqa: F401

reference = 'xing4'

_KEYS = ('vocab_size', 'hidden_size', 'intermediate_size',
         'moe_intermediate_size', 'num_hidden_layers',
         'first_k_dense_replace', 'moe_layer_freq', 'num_attention_heads',
         'num_key_value_heads', 'q_lora_rank', 'kv_lora_rank',
         'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim', 'rope_theta',
         'rope_interleave', 'rope_scaling', 'n_routed_experts',
         'n_shared_experts', 'num_experts_per_tok', 'norm_topk_prob',
         'routed_scaling_factor', 'scoring_func', 'topk_method', 'n_group',
         'topk_group', 'rms_norm_eps', 'attention_bias',
         'max_position_embeddings', 'tie_word_embeddings', 'hc_mult',
         'hc_sinkhorn_iters', 'hc_eps', 'mhc_h_res_clamp_min',
         'mhc_h_res_clamp_max')

_HC = ('phi', 'a_pre', 'a_post', 'a_res', 'b_pre', 'b_post', 'b_res')


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.xing4 import Xing4Config, Xing4ForCausalLM
    conf = Xing4Config(**{k: cfg[k] for k in _KEYS}, **extra)
    with paddle.LazyGuard():
        return Xing4ForCausalLM(conf)


def name_map(cfg):
    from benchmarks.reference.xing4 import is_expert_layer
    out = {'model.embed_tokens.weight': ('embed', None),
           'model.norm.weight': ('norm', None),
           'lm_head.weight': ('head', None)}
    common = {'input_layernorm.weight': 'in_norm',
              'post_attention_layernorm.weight': 'post_norm',
              'self_attn.q_a_proj.weight': 'qa_w',
              'self_attn.q_a_layernorm.weight': 'q_norm',
              'self_attn.q_b_proj.weight': 'qb_w',
              'self_attn.kv_a_proj_with_mqa.weight': 'kva_w',
              'self_attn.kv_a_layernorm.weight': 'kv_norm',
              'self_attn.kv_b_proj.weight': 'kvb_w',
              'self_attn.o_proj.weight': 'o_w'}
    common.update({f'{hc}.{leaf}': f'{hc}.{leaf}'
                   for hc in ('hc_attn', 'hc_mlp') for leaf in _HC})
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
        names = {**common, **(sparse if is_expert_layer(cfg, i) else dense)}
        for prog, canon in names.items():
            out[f'model.layers.{i}.{prog}'] = (f'l{i}.{canon}', None)
    return out
