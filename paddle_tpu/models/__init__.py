"""Model zoo: flagship LMs (GPT/BERT) + vision models re-export."""
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertForTokenClassification,
    BertModel,
    BertPretrainLoss,
    bert_base,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForPretraining,
    ErnieForSequenceClassification,
    ErnieModel,
    ErniePretrainLoss,
    ernie_base,
    knowledge_mask,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainLoss,
    gpt2_medium,
    gpt2_small,
)
from .hf_bridge import (  # noqa: F401
    bert_from_huggingface,
    ernie_from_huggingface,
    gpt2_from_huggingface,
    gpt2_to_huggingface,
)


# the solar_open2 and axk1 families are imported when first asked for: serving
# GPT-2 pays nothing for them at start-up (PERF.md: PR 27's imports cost 7.9 % of setup_s)
_LAZY = {"SolarOpen2Config": "solar_open2",
         "SolarOpen2ForCausalLM": "solar_open2",
         "AXK1Config": "axk1", "AXK1ForCausalLM": "axk1"}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module("." + _LAZY[name], __name__),
                       name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
