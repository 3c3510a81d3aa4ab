"""`nlp/ling3.py Ling3ForCausalLM` from a configuration file. The
canonical leaves are per layer and have the program's own shapes (the
experts HELD stacked [held, h, f]), so `fill` slices nothing. The file's
`num_experts` counts the experts held here; `expert_share` gives the
router's width and the first of them. The two lists of SwiGLU limits
stay whole in the file, one entry a PUBLISHED layer: the layers built
are `kept_layers`, and theirs are what the model is given."""
from __future__ import annotations

reference = 'ling3'

_KEYS = ('vocab_size', 'hidden_size', 'intermediate_size',
         'moe_intermediate_size', 'moe_shared_expert_intermediate_size',
         'num_hidden_layers', 'first_k_dense_replace', 'layer_group_size',
         'layer_types', 'num_attention_heads', 'num_key_value_heads',
         'head_dim', 'kv_lora_rank', 'q_lora_rank', 'qk_nope_head_dim',
         'qk_rope_head_dim', 'v_head_dim', 'rope_theta', 'rope_interleave',
         'rope_scaling', 'gated_attention_proj_granularity_type',
         'short_conv_kernel_size', 'kda_safe_gate', 'kda_lower_bound',
         'use_kda_lora', 'use_mla_nope', 'num_kv_heads_for_linear_attn',
         'num_experts', 'num_experts_per_tok', 'num_shared_experts',
         'n_group', 'topk_group', 'norm_topk_prob', 'routed_scaling_factor',
         'score_function', 'moe_router_enable_expert_bias', 'use_nGPT',
         'value_norm', 'up_proj_norm', 'rms_norm_eps',
         'max_position_embeddings', 'tie_word_embeddings')

_LIMITS = ('expert_swiglu_limit_list', 'share_expert_swiglu_limit_list')


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.ling3 import Ling3Config, Ling3ForCausalLM
    kw = {k: cfg[k] for k in _KEYS}
    # `aot.py --layers N` cuts the depth for a quick look
    n = kw['num_hidden_layers']
    kw['layer_types'] = kw['layer_types'][:n]
    kept = cfg.get('kept_layers', list(range(n)))[:n]
    for name in _LIMITS:
        if cfg.get(name) is not None:
            kw[name] = [cfg[name][i] for i in kept]
    share = cfg['expert_share']
    conf = Ling3Config(num_routed_experts=share['routed'],
                       first_expert=share['first'], **kw, **extra)
    with paddle.LazyGuard():
        return Ling3ForCausalLM(conf)


def name_map(cfg):
    from benchmarks.reference.ling3 import MLA, is_expert_layer
    out = {'model.embed_tokens.weight': ('embed', None),
           'model.norm.weight': ('norm', None),
           'lm_head.weight': ('head', None)}
    norms = {'input_layernorm.weight': 'in_norm',
             'post_attention_layernorm.weight': 'post_norm'}
    latent = {'self_attn.q_proj.weight': 'q_w',
              'self_attn.kv_a_proj_with_mqa.weight': 'kva_w',
              'self_attn.kv_a_layernorm.weight': 'kv_norm',
              'self_attn.kv_b_proj.weight': 'kvb_w',
              'self_attn.gate_proj.weight': 'gate_w',
              'self_attn.o_proj.weight': 'o_w'}
    kda = {'self_attn.q_proj.weight': 'kq_w',
           'self_attn.k_proj.weight': 'kk_w',
           'self_attn.v_proj.weight': 'kv_w',
           'self_attn.q_conv': 'q_conv', 'self_attn.k_conv': 'k_conv',
           'self_attn.v_conv': 'v_conv',
           'self_attn.f_proj.weight': 'f_w', 'self_attn.A_log': 'a_log',
           'self_attn.dt_bias': 'dt_bias',
           'self_attn.b_proj.weight': 'b_w',
           'self_attn.g_proj.weight': 'g_w',
           'self_attn.o_norm.weight': 'o_norm',
           'self_attn.o_proj.weight': 'ko_w'}
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
    layer_types = cfg['layer_types'][:cfg['num_hidden_layers']]
    for i, kind in enumerate(layer_types):
        names = {**norms, **(latent if kind == MLA else kda),
                 **(sparse if is_expert_layer(cfg, i) else dense)}
        for prog, canon in names.items():
            out[f'model.layers.{i}.{prog}'] = (f'l{i}.{canon}', None)
    return out
