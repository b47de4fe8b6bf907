"""A configuration file of the benchmark, read once: the published sizes
(Hugging Face `config.json` keys), what was cut, and how the served
program is set up for it."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    vocab_size: int
    rope_theta: float
    rms_norm_eps: float
    tie_word_embeddings: bool
    arch: str          # the program's architecture registry name
    quant: str         # the program's quantization preset, served as named
    backend: str       # the program's matmul backend on the chip
    reference: str     # module under perfbench/configs holding the reference

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def padded_vocab(self) -> int:
        """Rows of the served embedding table: the program pads the
        vocabulary to a multiple of 256 and masks the pad logits."""
        return -(-self.vocab_size // 256) * 256


def load(path: Path, name: str) -> ModelConfig:
    raw = json.loads(Path(path).read_text())
    prog = raw["program"]
    return ModelConfig(
        name=name,
        hidden_size=int(raw["hidden_size"]),
        intermediate_size=int(raw["intermediate_size"]),
        num_hidden_layers=int(raw["num_hidden_layers"]),
        num_attention_heads=int(raw["num_attention_heads"]),
        num_key_value_heads=int(raw["num_key_value_heads"]),
        vocab_size=int(raw["vocab_size"]),
        rope_theta=float(raw["rope_theta"]),
        rms_norm_eps=float(raw["rms_norm_eps"]),
        tie_word_embeddings=bool(raw["tie_word_embeddings"]),
        arch=prog["arch"], quant=prog["quant"], backend=prog["backend"],
        reference=raw["reference"])
