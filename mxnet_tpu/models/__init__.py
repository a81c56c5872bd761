"""Model library.

`mxnet_tpu.models` re-exports the gluon vision zoo (reference
python/mxnet/gluon/model_zoo/) and adds the transformer/BERT family
(reference counterpart: GluonNLP BERT built on contrib transformer ops,
src/operator/contrib/transformer.cc) as the flagship TP/SP-shardable model.
"""
from ..gluon.model_zoo.vision import (get_model, alexnet, resnet18_v1,
                                      resnet34_v1, resnet50_v1, resnet101_v1,
                                      resnet152_v1, resnet18_v2, resnet34_v2,
                                      resnet50_v2, resnet101_v2, resnet152_v2,
                                      vgg11, vgg13, vgg16, vgg19, vgg16_bn,
                                      mobilenet1_0, mobilenet_v2_1_0,
                                      squeezenet1_0, densenet121, inception_v3)
from .lenet import LeNet, lenet
from .mlp import MLP, mlp
from .bert import (BertModel, BertEncoder, TransformerEncoderCell,
                   bert_base, bert_large, bert_tiny)
from .moe_transformer import (MoEPositionwiseFFN, MoETransformerCell,
                              MoETransformerLM, moe_transformer_tiny)
from .hybrid_decoder import (HybridDecoder, HybridDecoderLayer, Mamba2Mixer,
                             GroupedQueryAttention, SwiGLU, HeldExpertsFFN,
                             hybrid_decoder_tiny, windowed_moe_decoder_tiny,
                             indexed_moe_decoder_tiny)

__all__ = ["get_model", "LeNet", "lenet", "MLP", "mlp", "BertModel",
           "BertEncoder", "TransformerEncoderCell", "bert_base", "bert_large",
           "bert_tiny", "MoEPositionwiseFFN", "MoETransformerCell",
           "MoETransformerLM", "moe_transformer_tiny", "HybridDecoder",
           "HybridDecoderLayer", "Mamba2Mixer", "GroupedQueryAttention",
           "SwiGLU", "HeldExpertsFFN", "hybrid_decoder_tiny",
           "windowed_moe_decoder_tiny", "indexed_moe_decoder_tiny"]
