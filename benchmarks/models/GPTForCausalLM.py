"""`nlp/gpt.py GPTForCausalLM` from a configuration file."""
from __future__ import annotations

reference = 'gpt'


def build(cfg, **extra):
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
    conf = GPTConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        intermediate_size=cfg['intermediate_size'],
        hidden_act=cfg['hidden_act'],
        hidden_dropout_prob=cfg['hidden_dropout_prob'],
        attention_probs_dropout_prob=cfg['attention_probs_dropout_prob'],
        max_position_embeddings=cfg['max_position_embeddings'],
        layer_norm_epsilon=cfg['layer_norm_epsilon'],
        tie_word_embeddings=cfg['tie_word_embeddings'], **extra)
    with paddle.LazyGuard():
        return GPTForCausalLM(conf)


def name_map(cfg):
    out = {'gpt.word_embeddings.weight': ('wte', None),
           'gpt.position_embeddings.weight': ('wpe', None),
           'gpt.final_norm.weight': ('lnf_w', None),
           'gpt.final_norm.bias': ('lnf_b', None)}
    per_layer = {
        'norm1.weight': 'ln1_w', 'norm1.bias': 'ln1_b',
        'attn.qkv_proj.weight': 'qkv_w', 'attn.qkv_proj.bias': 'qkv_b',
        'attn.out_proj.weight': 'out_w', 'attn.out_proj.bias': 'out_b',
        'norm2.weight': 'ln2_w', 'norm2.bias': 'ln2_b',
        'linear1.weight': 'fc1_w', 'linear1.bias': 'fc1_b',
        'linear2.weight': 'fc2_w', 'linear2.bias': 'fc2_b'}
    for i in range(cfg['num_hidden_layers']):
        for prog, canon in per_layer.items():
            out[f'gpt.layers.{i}.{prog}'] = (f'layers.{canon}', i)
    return out
