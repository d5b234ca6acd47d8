"""Every cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds.

The files are the committed ones with the sizes shrunk: the same
drivers, kernels, references, traffic shape and limits.
"""
from __future__ import annotations

import copy

from bench import run
from bench.common import ROOT, load_json

KERNEL_SIZES = {
    "scale": {"n": 300_000}, "triad": {"n": 300_000}, "axpy": {"n": 300_000},
    "flash_decode": {"b": 1, "kh": 2, "s": 512, "kv_len": 512},
    "spmv_bell": {"rows": 64, "blocks_per_row": 4, "block_cols": 16},
}
STENCIL_GRID = {2: [64, 256], 3: [16, 16, 128]}
LM_SIZES = {"hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "num_hidden_layers": 2, "vocab_size": 512}
LM_TRAFFIC = {"clients": 8, "prompt_len": 16, "gen": 16}
#: the control's size: published widths, 2 layers, a 16k vocabulary.  At
#: 128 wide the logits are 5x smaller and the float8 control's gap stays
#: under the limit set at the cell's own size.
LM_CONTROL_SIZES = {"num_hidden_layers": 2, "vocab_size": 16384}
LM_CONTROL_TRAFFIC = {"clients": 4, "prompt_len": 64, "gen": 32}


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def files(workload: str, control: bool = False) -> dict:
    """``run.cell_spec`` of ``workload`` at the small size (an LM cell's
    control at its own larger one)."""
    f = copy.deepcopy(run.cell_spec(spec(), workload))
    cfg = f["config"]
    if cfg["driver"] == "kernel_mix":
        for e in cfg["kernels"]:
            if e["kind"] == "stencil":
                e["grid"] = STENCIL_GRID[len(e["grid"])]
            e.update(KERNEL_SIZES.get(e["kind"], {}))
    elif control:
        cfg.update(LM_CONTROL_SIZES)
        f["traffic"].update(LM_CONTROL_TRAFFIC)
    else:
        cfg.update(LM_SIZES)
        f["traffic"].update(LM_TRAFFIC)
    return f
