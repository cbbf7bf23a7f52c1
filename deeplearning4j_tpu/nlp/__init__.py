"""NLP: tokenization, vocab, BERT data pipeline, embedding models.

Reference: deeplearning4j-nlp-parent/deeplearning4j-nlp (SURVEY.md §2.5 NLP
row): tokenizers incl. BertWordPieceTokenizer, BertIterator, Word2Vec.
"""
from deeplearning4j_tpu.nlp.tokenization import (BertWordPieceTokenizer,  # noqa: F401
                                                 BertWordPieceTokenizerFactory,
                                                 DefaultTokenizer,
                                                 DefaultTokenizerFactory)
from deeplearning4j_tpu.nlp.bert_iterator import BertIterator  # noqa: F401
from deeplearning4j_tpu.nlp.jamba import JambaConfig, JambaLM  # noqa: F401
from deeplearning4j_tpu.nlp.keye_vl import KeyeVLConfig, KeyeVLLM  # noqa: F401
from deeplearning4j_tpu.nlp.ling import LingConfig, LingLM  # noqa: F401
from deeplearning4j_tpu.nlp.nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHLM)
from deeplearning4j_tpu.nlp.olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig, OlmoHybridLM)
from deeplearning4j_tpu.nlp.pangu_moe import (  # noqa: F401
    PanguMoEConfig, PanguMoELM)
from deeplearning4j_tpu.nlp.sambay import SambaYConfig, SambaYLM  # noqa: F401
from deeplearning4j_tpu.nlp.transformer import (  # noqa: F401
    TransformerLM, TransformerLMConfig)
from deeplearning4j_tpu.nlp.word2vec import (  # noqa: F401
    FastText, Glove, ParagraphVectors, VocabCache, Word2Vec, WordVectors,
    WordVectorSerializer)
