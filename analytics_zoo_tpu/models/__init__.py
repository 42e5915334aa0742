"""Model zoo — parity with ref zoo/.../models (SURVEY.md §2.1 model-zoo rows).

Families: image classification (ResNet-50 catalog), object detection (SSD),
recommendation (NeuralCF, WideAndDeep), anomaly detection, text
classification, text matching (KNRM), seq2seq, and a causal decoder-only
language model (CausalLM) over the decoder block library.
"""

from analytics_zoo_tpu.models.common import ZooModel, Ranker
from analytics_zoo_tpu.models.textclassification import TextClassifier
from analytics_zoo_tpu.models.recommendation import (
    NeuralCF, WideAndDeep, ColumnFeatureInfo, Recommender, SessionRecommender,
)
from analytics_zoo_tpu.models.anomalydetection import AnomalyDetector
from analytics_zoo_tpu.models.seq2seq import Seq2seq
from analytics_zoo_tpu.models.textmatching import KNRM
from analytics_zoo_tpu.models.causal_lm import CausalLM

__all__ = [
    "ZooModel", "Ranker", "TextClassifier", "NeuralCF", "WideAndDeep",
    "ColumnFeatureInfo", "Recommender", "SessionRecommender",
    "AnomalyDetector", "Seq2seq", "KNRM", "CausalLM",
]
