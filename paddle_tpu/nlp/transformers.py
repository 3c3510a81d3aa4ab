"""Import-path parity with the reference's `paddlenlp.transformers`."""
from .afmoe import AfmoeConfig, AfmoeForCausalLM, AfmoeModel  # noqa: F401
from .bert import (BertConfig, BertForMaskedLM,  # noqa: F401
                   BertForSequenceClassification, BertModel)
from .deepseek_v3 import (DeepseekV3Config,  # noqa: F401
                          DeepseekV3ForCausalLM, DeepseekV3Model)
from .ernie import (ErnieConfig, ErnieForMaskedLM,  # noqa: F401
                    ErnieForSequenceClassification, ErnieModel)
from .generation import GenerationMixin  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .jamba import (JambaConfig, JambaForCausalLM,  # noqa: F401
                    JambaModel)
from .lfm2 import (Lfm2MoeConfig, Lfm2MoeForCausalLM,  # noqa: F401
                   Lfm2MoeModel)
from .ling3 import (Ling3Config, Ling3ForCausalLM,  # noqa: F401
                    Ling3Model)
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from .mimo_v2 import (MiMoV2Config, MiMoV2ForCausalLM,  # noqa: F401
                      MiMoV2Model)
from .t5 import (T5Config, T5ForConditionalGeneration,  # noqa: F401
                 T5Model)
from .tokenizer import (BPETokenizer, PretrainedTokenizer,  # noqa: F401
                        WhitespaceTokenizer)
from .xing4 import (Xing4Config, Xing4ForCausalLM,  # noqa: F401
                    Xing4Model)
