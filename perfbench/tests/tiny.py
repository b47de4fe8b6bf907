"""A cell small enough for a CPU: the qwen2 architecture at toy widths
under a toy closed loop, served on the `xla` backend."""
from perfbench.lib.cell import Cell
from perfbench.lib.model_config import ModelConfig

CONFIG = ModelConfig(
    name="tiny", hidden_size=64, intermediate_size=128, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=500,
    rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=False,
    arch="qwen2-7b", quant="olive_serve", backend="xla",
    reference="qwen2_ref")

TRAFFIC = {
    "name": "tiny_closed", "loop": "closed", "clients": 4, "slots": 4,
    "page_size": 16, "prefill_chunk": 16, "max_len": 160,
    "prompt_len": {"dist": "log_uniform", "min": 16, "max": 64},
    "output_len": {"dist": "uniform", "min": 64, "max": 96},
    "requests": 64, "check_requests": 6,
}


# Between what sound runs of this toy read (median rank 4-8 on six
# seeds, 438-501 tokens each) and the least that a fault or the 3-bit
# control reads (50, the control; the faults 58-278).
LIMIT = 20.0


def cell(limit: float = LIMIT) -> Cell:
    return Cell(name="tiny.closed", chips=1, config=CONFIG, traffic=TRAFFIC,
                end_to_end=[{"name": "out_tok_s", "unit": "tokens/s"},
                            {"name": "itl_p95_ms", "unit": "ms"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[], limits={"median_rank": limit})
