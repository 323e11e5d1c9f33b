"""In-tree model families.

Vision models live in gluon.model_zoo.vision (reference layout); BERT and
the NMT transformer lived in GluonNLP/Sockeye for the reference and are
in-tree here since they are baseline configs (BASELINE.md configs 3-5).
"""
from . import transformer_blocks
from . import bert
from . import transformer
from . import decoder_lm
from .bert import (BERTEncoder, BERTModel, BERTForPretrain,
                   BERTPretrainLoss, BERTForQA,
                   BERTClassifier, bert_12_768_12, bert_24_1024_16,
                   get_bert_model)
from .transformer import (Transformer, TransformerEncoder,
                          TransformerDecoder, transformer_base,
                          transformer_big, SmoothedSoftmaxCELoss)
from .transformer_blocks import TransformerDecoderLM
from .decoder_lm import DecoderLM, get_decoder_lm

__all__ = ["BERTEncoder", "BERTModel", "BERTForPretrain",
           "BERTPretrainLoss", "BERTForQA",
           "BERTClassifier", "bert_12_768_12", "bert_24_1024_16",
           "get_bert_model", "Transformer", "TransformerEncoder",
           "TransformerDecoder", "transformer_base", "transformer_big",
           "SmoothedSoftmaxCELoss", "TransformerDecoderLM", "DecoderLM",
           "get_decoder_lm"]
