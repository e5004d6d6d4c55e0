"""A cell's spec cut to a size the CPU runs in seconds.

Only the sizes change; the drivers, the reference and the check are the
ones the chip runs.  The device record stands in for the look for a
chip (``bench/run.py``'s ``device_info``), which refuses the CPU.
"""
from __future__ import annotations

from bench.lib import names

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def spec(workload: str) -> dict:
    s = names.cell_spec(workload)
    cfg, mix, eng = s["config"], s["traffic"], s["workload"]["engine"]
    if cfg["family"] == "llama":
        cfg.update(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2,
                   num_hidden_layers=2, vocab_size=256)
        eng.update(slots=4, max_len=256, prefill_chunk=32)
        if mix["loop"] == "open":
            mix.update(rate_per_s=6.0, ramp_s=0.5)
            mix["prompt_tokens"].update(median=40, min=8, max=120)
            mix["output_tokens"].update(median=8, min=2, max=32)
        else:
            mix.update(clients=4)
            mix["prompt_tokens"].update(min=16, max=64)
    else:
        cfg.update(depth=2, hidden_size=64, num_heads=4, input_size=16,
                   num_classes=10)
        eng.update(batch=2)
        mix.update(num_steps=3, classes=10)
    return s
