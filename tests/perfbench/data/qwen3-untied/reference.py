"""The test family's reference: Qwen3's, whose weights and head follow
``tie_word_embeddings``."""
from perfbench.reference.qwen3 import make_logits, params  # noqa: F401
