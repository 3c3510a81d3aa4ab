"""`nlp/llama.py LlamaForCausalLM` from a configuration file (the
Llama-style block at another family's published sizes)."""
from __future__ import annotations

reference = 'llama'


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
    conf = LlamaConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        intermediate_size=cfg['intermediate_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        max_position_embeddings=cfg['max_position_embeddings'],
        rms_norm_eps=cfg['rms_norm_eps'], rope_theta=cfg['rope_theta'],
        tie_word_embeddings=cfg['tie_word_embeddings'], **extra)
    with paddle.LazyGuard():
        return LlamaForCausalLM(conf)


def name_map(cfg):
    out = {'llama.embed_tokens.weight': ('embed', None),
           'llama.norm.weight': ('norm', None),
           'lm_head.weight': ('head', None)}
    per_layer = {
        'input_layernorm.weight': 'in_norm',
        'self_attn.q_proj.weight': 'q_w', 'self_attn.k_proj.weight': 'k_w',
        'self_attn.v_proj.weight': 'v_w', 'self_attn.o_proj.weight': 'o_w',
        'post_attention_layernorm.weight': 'post_norm',
        'mlp.gate_proj.weight': 'gate_w', 'mlp.up_proj.weight': 'up_w',
        'mlp.down_proj.weight': 'down_w'}
    for i in range(cfg['num_hidden_layers']):
        for prog, canon in per_layer.items():
            out[f'llama.layers.{i}.{prog}'] = (f'layers.{canon}', i)
    return out
