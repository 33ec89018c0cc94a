"""CPU tests of the benchmark at smoke sizes (run them explicitly:
``python -m pytest bench/tests``; the repository's own suite does not
collect them)."""

import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

def smoke_sizes(config: str, width: int, vocab: int):
    """(bench sizes, program overrides) of a two-layer model."""
    if config == "paper-lstm":
        return ({"num_layers": 2, "hidden_size": width, "rnn_hidden_size": width,
                 "vocab_size": vocab},
                {"n_layers": 2, "d_model": width, "rnn_hidden": width, "vocab": vocab})
    return ({"num_hidden_layers": 2, "hidden_size": width, "intermediate_size": 2 * width,
             "state_size": 8, "time_step_rank": -(-width // 16), "vocab_size": vocab},
            {"n_layers": 2, "d_model": width, "ssm_state": 8, "vocab": vocab})


def smoke_cell(workload: str, grid=(8, 16), outputs=(8, 16), slots=4,
               width=64, vocab=256):
    """The cell as BENCHMARK.json defines it, cut to a CPU-sized model and
    mix; the harness and the reference are the real ones."""
    from bench import harness

    cell = harness.load_cell(workload)
    cfg = copy.deepcopy(cell.config)
    sizes, over = smoke_sizes(cfg["name"], width, vocab)
    cfg["sizes"].update(sizes)
    cfg["program"]["overrides"].update(over)
    mix = copy.deepcopy(cell.mix)
    mix["prompt"].update(min=grid[0], max=grid[-1], median=grid[0], grid=list(grid))
    mix["output"].update(min=outputs[0], max=outputs[1], median=outputs[0])
    mix["slots"] = slots
    mix["warm_seconds"] = 0.5
    if mix["loop"] == "closed":
        mix["clients"] = slots
    data = copy.deepcopy(cell.data)
    if "rate_rps" in data:
        data["rate_rps"] = 8.0
    data["limits"]["min_compared_tokens"] = 4
    data["limits"]["sample_requests"] = 16
    cell.config, cell.mix, cell.data = cfg, mix, data
    return cell
