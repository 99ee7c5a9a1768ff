"""Each cell at a size a test run holds: the same files and code, with
the configuration's widths and depth and the traffic's sizes cut."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

SIZES = {
    "smollm-360m.train4k": (
        dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=256),
        dict(clients=4, seq_len=32, distinct_batches=3)),
    "femnist-cnn.cohort32": (
        dict(conv1_channels=4, conv2_channels=8, hidden=32),
        dict(train_writers=6, mean_samples=12, clients_per_round=4,
             support_size=4, query_size=4, rounds_per_call=2)),
}
CELLS = sorted(SIZES)
LOOSE = {"loss_gap": 1.0, "grad_norm_gap": 1.0, "update_norm_gap": 1.0}


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.Cell(name)
    config, traffic = SIZES[name]
    cell.config = dict(cell.config, **config)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell
