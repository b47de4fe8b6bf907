"""A configuration file of the benchmark, read once: the published sizes
(Hugging Face `config.json` keys), what was cut, and how the served
program is set up for it.

The file also names its architecture module (`reference`): the one place
that knows the architecture's weights, the program's layout and sizes of
them, its matmul calls and operations, and its plain reference. The
module reads whatever keys of the file it needs from `raw`."""
from __future__ import annotations

import dataclasses
import importlib
import json
import types
from pathlib import Path
from typing import Mapping


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
    reference: str     # the architecture module: a name under
    #                    perfbench/configs, or a full dotted module path
    head_dim: int = 0  # 0: hidden_size // num_attention_heads
    mesh: tuple = ()   # the program's (data, model) mesh on several chips
    # the whole file, read-only, for the architecture module
    raw: Mapping = dataclasses.field(default_factory=dict, compare=False,
                                     repr=False)

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.hidden_size
                               // self.num_attention_heads)

    @property
    def padded_vocab(self) -> int:
        """Rows of the served embedding table: the program pads the
        vocabulary to a multiple of 256 and masks the pad logits."""
        return -(-self.vocab_size // 256) * 256


def architecture(cfg: ModelConfig) -> types.ModuleType:
    """The module the file names under `reference`, giving `make_weights`,
    `to_program_tree`, `program_sizes`, `layer_matmuls`, `flops_per_token`
    and `logits`."""
    name = cfg.reference
    return importlib.import_module(
        name if "." in name else f"perfbench.configs.{name}")


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
        reference=raw["reference"],
        head_dim=int(raw.get("head_dim", 0)),
        mesh=tuple(int(v) for v in prog.get("mesh", ())),
        raw=types.MappingProxyType(raw))
