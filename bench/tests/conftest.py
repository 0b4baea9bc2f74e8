"""CPU tests of the benchmark's yardstick. Run from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

# a size a CPU test run holds; every shape key of the cells' files
TINY = {
    "resnet18": {"image_size": 32, "stem_width": 8, "stage_sizes": [1, 1],
                 "stage_widths": [8, 16], "num_classes": 10},
    "qwen2-1.5b": {"hidden_size": 64, "intermediate_size": 128,
                   "num_hidden_layers": 2, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "vocab_size": 256, "torch_dtype": "float32",
                   "engine": {"max_batch": 4, "max_seq": 128,
                              "block_size": 16}},
}
TINY_PROMPT = {"median": 16, "min": 8, "max": 32, "ladder": [8, 16, 32]}
TINY_OUTPUT = {"median": 8, "min": 4, "max": 16}


# cells the tests drive: configuration and traffic mix of each
CELLS = {"resnet18.steady": ("resnet18", "resnet_steady"),
         "resnet18.bulk": ("resnet18", "resnet_bulk"),
         "qwen2-1.5b.chat": ("qwen2-1.5b", "lm_chat")}


def tiny_cell(name: str):
    """A cell's configuration and mix, at a CPU test's size."""
    from harness import registry
    cell = registry.build(name, *CELLS[name])
    cell.cfg.update(copy.deepcopy(TINY[cell.cfg["name"]]))
    mix = copy.deepcopy(cell.mix)
    mix["ramp_s"] = 1.0
    if "prompt" in mix["request"]:
        mix["rate_per_s"] = 4.0
        mix["request"]["prompt"].update(TINY_PROMPT)
        mix["request"]["output"].update(TINY_OUTPUT)
    elif mix["loop"] == "open":
        mix["rate_per_s"] = 20.0
    cell.mix = mix
    return cell


@pytest.fixture
def fresh_programs():
    """Compiled programs are cached process-wide by the program; a test
    that breaks the program must not see, or leave, a sound one there."""
    from repro.core.executor import Executor
    Executor._batch_cache.clear()
    yield
    Executor._batch_cache.clear()
