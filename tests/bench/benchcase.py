"""A cell small enough for the CPU: the qwen2.5-3b configuration and the
map mix with every width and length cut down.  Shared by the tests of
``bench/`` that drive a whole run."""
from bench import cells
from bench.traffic import load_mix

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def small_cell(width: int = 64, layers: int = 1,
               vocab: int = 512) -> cells.Cell:
    cfg = cells.load_config("qwen2.5-3b")
    cfg.update(hidden_size=width, intermediate_size=2 * width,
               num_hidden_layers=layers, num_attention_heads=4,
               num_key_value_heads=2, head_dim=width // 4, vocab_size=vocab,
               max_len=256, slots=4)
    mix = load_mix("map")
    mix.update(workflows=2, scripts=4, document={"lognormal": [5.0, 0.3]},
               chunk_tokens=60, chunks={"min": 2, "max": 3},
               map_prompt={"base": 60, "exp_mean": 10, "multiple": 64,
                           "cap": 64},
               map_output={"base": 24, "exp_mean": 4}, reduce_item=8,
               reduce_instruction=8, reduce_output=8,
               final_output={"base": 12, "exp_mean": 4})
    e2e, layer = cells.metrics_of(cells.load_benchmark(), "qwen3b-map")
    return cells.Cell("small", 1, cfg, mix, e2e, layer)
