from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTForPretraining,
    GPTLMHeadModel,
    GPTModel,
    GPTPretrainingCriterion,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForMaskedLM,
    ErnieForSequenceClassification,
    ErnieForTokenClassification,
    ErnieModel,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    LlamaModel,
)
from .ouro import (  # noqa: F401
    OuroConfig,
    OuroDecoderLayer,
    OuroForCausalLM,
    OuroModel,
)
from .sdar import (  # noqa: F401
    SDARConfig,
    SDARDecoderLayer,
    SDARForCausalLM,
    SDARModel,
)
