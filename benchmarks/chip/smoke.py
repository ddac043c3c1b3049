"""Small widths and sizes for running the harness on the CPU in tests.

``shrink(cell)`` keeps a cell's files and changes only sizes, so every
driver, reference and metric file runs as it would on the chip."""
from __future__ import annotations

import dataclasses

WIDTHS = {
    "olmo": dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=4, head_dim=16, intermediate_size=128, vocab_size=256),
    "rwkv6": dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, head_size=16, intermediate_size=128,
                  vocab_size=256),
}
SIZES = {
    "serve_static": dict(batch=4, prompt_len=32, new_tokens=6, check_sequences=4, check_block=2),
    "train": dict(batch=2, seq_len=32, feed_batches=6, mean_doc_len=8),
}


def shrink(cell):
    config = dict(cell.config, **WIDTHS[cell.config["reference"]])
    traffic = dict(cell.traffic, **SIZES[cell.traffic["driver"]])
    return dataclasses.replace(cell, config=config, traffic=traffic)


def use(harness, monkeypatch) -> None:
    """Make ``harness.cell`` return shrunk cells for the rest of a test."""
    real = harness.cell
    monkeypatch.setattr(harness, "cell", lambda name: shrink(real(name)))
