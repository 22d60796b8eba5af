"""Model zoo (ref ``python/paddle/vision/models`` + PaddleNLP GPT/ERNIE)."""

from .gpt import (GPTConfig, GPTForCausalLM, GPTModel, gpt_config,  # noqa: F401
                  param_sharding_spec)
from .bert import (BertConfig, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, BertModel, ErnieModel,
                   ErnieForPretraining, ErnieForSequenceClassification,
                   bert_config, bert_mlm_pipeline, bert_param_sharding_spec,
                   ernie_config, masked_mlm_loss)
from .qwen3_next import (Qwen3NextConfig, Qwen3NextForCausalLM,  # noqa: F401
                         qwen3_next_sharding_spec)
from .bailing_hybrid import (BailingHybridConfig,  # noqa: F401
                             BailingHybridForCausalLM,
                             bailing_hybrid_sharding_spec)
from .keye_vl2 import (KeyeVL2Config, KeyeVL2ForCausalLM,  # noqa: F401
                       keye_vl2_sharding_spec)
