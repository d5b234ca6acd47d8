"""The deepseek-v2-lite cell at a small size on the CPU, and its counts.

The cell runs under ``bench/tests/small.py`` as it stands: the small
sizes override the Llama keys only (hidden size, dense width, heads,
layers, vocabulary), so the MLA and MoE keys stay at their published
values (latent rank 512, rotary 64, 8 held experts of width 1,408, a
64-wide router, top-6, 2 shared).  ``test_cells.py`` gives the cell its
sound, control and fault cases; here are the faults particular to a
held-expert layer, and the counts against a hand reckoning.

Run by path: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import sys

import jax.numpy as jnp
import pytest

from bench import run
from bench.common import BENCH, ROOT, load_module
from bench.tests import small

sys.path.insert(0, str(ROOT / "src"))

CELL = "deepseek-v2-lite.chat-b128"
SEED = 2**31 + 101
COUNTS = load_module(BENCH / "counts" / "mla_moe.py")
#: the faults of the held experts' part are read at the cell's own depth
#: (the dense layer and 8 MoE layers): each MoE layer's held experts give
#: a few percent of its output, so one layer's fault moves the logits by
#: less than bfloat16 does, and the cell's 8 by well over the limit
DEPTH = 9


def execute(layers=None):
    """The cell at ``small.py``'s sizes; ``layers`` sets its depth."""
    files = small.files(CELL)
    if layers:
        files["config"]["num_hidden_layers"] = layers
    return run.execute(CELL, SEED, 0.2, False, spec=small.spec(),
                       files=files, device_check=False)


def test_small_sizes_keep_the_published_mla_and_moe_keys():
    cfg = small.files(CELL)["config"]
    assert cfg["hidden_size"] == 128 and cfg["num_hidden_layers"] == 2
    assert (cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["moe_intermediate_size"], cfg["n_routed_experts"],
            cfg["published"]["n_routed_experts"],
            cfg["num_experts_per_tok"]) == (512, 64, 1408, 8, 64, 6)


@pytest.mark.parametrize("layers", [None, DEPTH])
def test_small_cell_is_correct(layers):
    r = execute(layers)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0


def test_held_experts_part_zeroed_is_not_correct(monkeypatch):
    from repro.models import moe

    real = moe.held_experts

    def zeroed(*args, **kwargs):
        out, load = real(*args, **kwargs)
        return jnp.zeros_like(out), load
    monkeypatch.setattr(moe, "held_experts", zeroed)
    r = execute(DEPTH)
    assert not r["correct"], r["checks"]


def test_held_range_shifted_by_one_expert_is_not_correct(monkeypatch):
    from repro.models.config import ModelConfig

    real = ModelConfig.held_range

    def shifted(cfg):
        start, stop = real.fget(cfg)
        return start + 1, stop + 1
    monkeypatch.setattr(ModelConfig, "held_range", property(shifted))
    r = execute(DEPTH)
    assert not r["correct"], r["checks"]


def test_driver_refuses_a_program_without_held_experts(monkeypatch):
    import dataclasses

    from repro.models.config import ModelConfig

    driver = load_module(BENCH / "drivers" / "lm_moe_decode.py")
    fields = [f for f in dataclasses.fields(ModelConfig)
              if f.name != "held_experts"]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    with pytest.raises(RuntimeError, match="held_experts"):
        driver.model_config(small.files(CELL)["config"])


def test_counts_match_a_hand_reckoning_at_published_sizes():
    cfg = run.cell_spec(small.spec(), CELL)["config"]
    # one decode step, batch 128, 320 live positions, bfloat16
    held = 8 * 8 * 3 * 2048 * 1408 * 2          # 8 layers x 8 experts
    shared = 8 * 3 * 2048 * 2816 * 2
    router = 8 * 2048 * 64 * 4                   # float32
    mla = 9 * (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256
               + 16 * 128 * 2048) * 2
    latent = 9 * 128 * 320 * (512 + 64) * 2
    head = 2048 * 102400 * 2
    dense = 3 * 2048 * 10944 * 2
    want = held + shared + router + mla + latent + head + dense
    _, nbytes = COUNTS.decode_step(cfg, 128, 320, 2)
    assert nbytes == want
    assert nbytes / 819e9 == pytest.approx(3.19e-3, rel=2e-3)
    flops, gbytes = COUNTS.expert_gmm(cfg, 100, 1, 2)
    assert flops == 6 * 2048 * 1408 * 100
    assert gbytes == 3 * (8 * 2048 * 1408 + 100 * (2048 + 1408)) * 2
    # a prefill of 128 x 256 without the routed experts: about 22 TFLOP
    assert COUNTS.prefill_flops(cfg, 128, 256) == pytest.approx(22.1e12,
                                                                rel=0.02)
