from .gpt import GPT, GPTConfig, GPTForCausalLM  # noqa: F401
from .bert import Bert, BertConfig, BertForPretraining  # noqa: F401
from .ernie import (Ernie, ErnieConfig, ErnieForPretraining,  # noqa: F401
                    ernie_base, ernie_tiny, ernie_pipeline_descs)
from .deepseek_v2 import (DeepseekV2Config, DeepseekV2ForCausalLM,  # noqa: F401
                          deepseek_v2_tiny)
