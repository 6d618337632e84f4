"""A planted fault: the test family's reference with the head tied to the
embedding, on the family's own weights."""
from perfbench.reference import qwen3
from perfbench.reference.qwen3 import params  # noqa: F401


def make_logits(cfg: dict, low_precision: bool = False):
    return qwen3.make_logits(dict(cfg, tie_word_embeddings=True),
                             low_precision)
