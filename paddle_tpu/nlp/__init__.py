"""paddle_tpu.nlp — transformer model zoo + generation + tokenizers.

Upstream analogue: PaddleNLP `paddlenlp.transformers`. The `transformers`
submodule alias mirrors the reference's import path
(`from paddlenlp.transformers import LlamaForCausalLM` →
`from paddle_tpu.nlp.transformers import LlamaForCausalLM`).
"""
from __future__ import annotations

from .afmoe import AfmoeConfig, AfmoeForCausalLM, AfmoeModel
from .bert import (BertConfig, BertForMaskedLM,
                   BertForSequenceClassification, BertModel)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3ForCausalLM,
                          DeepseekV3Model)
from .ernie import (ErnieConfig, ErnieForMaskedLM,
                    ErnieForSequenceClassification, ErnieModel)
from .generation import GenerationMixin, Seq2SeqGenerationMixin
from .gpt import GPTConfig, GPTForCausalLM, GPTModel
from .jamba import JambaConfig, JambaForCausalLM, JambaModel
from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel
from .ling3 import Ling3Config, Ling3ForCausalLM, Ling3Model
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel)
from .mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM, MiMoV2Model
from .t5 import T5Config, T5ForConditionalGeneration, T5Model
from .xing4 import Xing4Config, Xing4ForCausalLM, Xing4Model
from .tokenizer import (BPETokenizer, PretrainedTokenizer,
                        WhitespaceTokenizer)

from . import transformers  # noqa: E402  (API-parity alias module)

__all__ = [
    'AfmoeConfig', 'AfmoeForCausalLM', 'AfmoeModel', 'BertConfig', 'BertForMaskedLM', 'BertForSequenceClassification',
    'BertModel', 'DeepseekV3Config', 'DeepseekV3ForCausalLM',
    'DeepseekV3Model', 'ErnieConfig', 'ErnieForMaskedLM',
    'ErnieForSequenceClassification', 'ErnieModel', 'GenerationMixin',
    'GPTConfig', 'GPTForCausalLM', 'GPTModel', 'JambaConfig',
    'JambaForCausalLM', 'JambaModel', 'Lfm2MoeConfig',
    'Lfm2MoeForCausalLM', 'Lfm2MoeModel', 'Ling3Config',
    'Ling3ForCausalLM', 'Ling3Model', 'LlamaConfig',
    'LlamaForCausalLM', 'LlamaModel', 'MiMoV2Config', 'MiMoV2ForCausalLM',
    'MiMoV2Model', 'Seq2SeqGenerationMixin',
    'T5Config', 'T5ForConditionalGeneration', 'T5Model', 'BPETokenizer',
    'PretrainedTokenizer', 'WhitespaceTokenizer', 'Xing4Config',
    'Xing4ForCausalLM', 'Xing4Model', 'transformers',
]
