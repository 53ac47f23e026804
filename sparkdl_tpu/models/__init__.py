from .bert import (BertConfig, BertEncoder, BertForSequenceClassification,
                   bert_finetune_loss, glue_loss_fn)
from .granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM
from .lfm2 import Lfm2Config, Lfm2ForCausalLM
from .llama import LlamaConfig, LlamaModel, lora_mask, lora_optimizer
from .lm_loss import causal_lm_loss_fn
from .phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
from .qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM
from .smallthinker import SmallThinkerConfig, SmallThinkerForCausalLM
from .pretrained import (CheckpointMismatch, cast_float_leaves,
                         import_hf_bert, import_hf_llama,
                         import_keras_inception, import_keras_resnet,
                         import_keras_vgg, import_keras_xception,
                         load_pretrained, merge_into_template, read_keras_h5)
from .tokenizer import ByteBPETokenizer
from .registry import (SUPPORTED_MODELS, NamedImageModel, decodePredictions,
                       get_model, load_safetensors, load_weights,
                       preprocess_caffe, preprocess_tf, preprocess_torch,
                       save_safetensors, save_weights)

__all__ = [
    "SUPPORTED_MODELS", "NamedImageModel", "get_model", "decodePredictions",
    "preprocess_tf", "preprocess_caffe", "preprocess_torch",
    "save_weights", "load_weights", "load_safetensors", "save_safetensors",
    "BertConfig", "BertEncoder", "BertForSequenceClassification",
    "glue_loss_fn", "bert_finetune_loss",
    "LlamaConfig", "LlamaModel", "causal_lm_loss_fn", "lora_mask",
    "lora_optimizer", "Lfm2Config", "Lfm2ForCausalLM", "Phi4FlashConfig",
    "Phi4FlashForCausalLM", "GraniteHybridConfig", "GraniteHybridForCausalLM",
    "Qwen3NextConfig", "Qwen3NextForCausalLM", "SmallThinkerConfig",
    "SmallThinkerForCausalLM",
    "load_pretrained", "import_hf_llama", "import_hf_bert",
    "import_keras_resnet", "import_keras_vgg", "import_keras_inception",
    "import_keras_xception",
    "read_keras_h5", "merge_into_template", "CheckpointMismatch",
    "ByteBPETokenizer", "cast_float_leaves",
]
