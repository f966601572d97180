"""Small cells for the CPU tests: the committed configurations, traffic
and limits with every size shrunk (widths included) so that a run takes
seconds on the host."""
from __future__ import annotations

import copy

from bench import harness


def _load(cell_name: str) -> harness.Cell:
    return harness.load_cell(harness.benchmark_spec(), cell_name)


def train_cell() -> harness.Cell:
    cell = copy.deepcopy(_load("mamba2-370m.tthf.d2d_every_step"))
    cell.config["model"].update(num_layers=2, d_model=64, vocab_size=500,
                                ssm_state_dim=16, ssm_head_dim=32,
                                ssm_num_heads=4, ssm_chunk=32)
    cell.traffic.update(seq_len=64, tau=2)
    return cell


def chat_cell() -> harness.Cell:
    cell = copy.deepcopy(_load("mamba2-370m.serve.chat_bursts"))
    cell.config["model"].update(num_layers=2, d_model=128, vocab_size=500,
                                ssm_state_dim=16, ssm_head_dim=32,
                                ssm_num_heads=8, ssm_chunk=32)
    cell.traffic["scheduler"].update(slots=4, max_prompt=64, max_total=96,
                                     prefill_chunk=32)
    cell.traffic["arrivals"].update(
        mean_rps=3.0, prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 64},
        output={"median": 8, "sigma": 0.8, "lo": 2, "hi": 32})
    return cell


def code_cell() -> harness.Cell:
    cell = copy.deepcopy(_load("starcoder2-3b.serve.decode_long"))
    cell.config["model"].update(num_layers=2, d_model=256, num_heads=4,
                                num_kv_heads=2, head_dim=64, d_ff=512,
                                vocab_size=512, sliding_window=96)
    cell.traffic["scheduler"].update(slots=4, max_prompt=96, max_total=128,
                                     prefill_chunk=32)
    cell.traffic["clients"].update(clients=4, first_prompt=[32, 96],
                                   reuse=[32, 64], tail=[8, 32],
                                   output=[8, 32])
    return cell
