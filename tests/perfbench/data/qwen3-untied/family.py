"""A test family: Qwen3 with an LM head of its own, untied from the token
embedding, as Qwen3-8B and larger have it. The program side is Qwen3's,
whose ``program_config`` passes ``tie_word_embeddings`` on."""
from perfbench.families.qwen3 import program_config, request_flops  # noqa: F401
