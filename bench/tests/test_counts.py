"""The benchmark's own counts equal the program's Eq. 2 traits today.

``bench/counts`` is the yardstick kept apart from the program; while
the program's ``traits`` and ``step_traits`` count the same work, the
two must agree exactly, at the configuration's dtype.
"""
import math
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench.common import BENCH, ROOT, load_json, load_module

sys.path.insert(0, str(ROOT / "src"))

CONFIG = load_json(BENCH / "configs" / "paper-kernels-f32.json")
LM = load_json(BENCH / "configs" / "deepseek-7b.json")


def counts(kind):
    return load_module(BENCH / "counts" / f"{kind}.py").count


def shaped(kind_module, entry):
    """The entry's inputs as shape-only arrays (nothing allocated)."""
    import jax
    return jax.eval_shape(lambda k: kind_module.make(k, entry, jnp.float32),
                          jax.random.key(0))


@pytest.mark.parametrize("entry", CONFIG["kernels"], ids=lambda e: e["name"])
def test_kernel_counts_equal_program_traits(entry):
    from repro.kernels import registry
    from repro.kernels.spmv.ref import BlockEll

    kind = load_module(BENCH / "kernels" / f"{entry['kind']}.py")
    op = registry.get(kind.OP)
    x = shaped(kind, entry)
    flops, nbytes = counts(entry["kind"])(entry, 4)
    if entry["kind"] in ("scale", "triad", "axpy"):
        args = {"scale": (x.get("b"), 1.5),
                "triad": (x.get("b"), x.get("c"), 1.5),
                "axpy": (1.5, x.get("x"), x.get("y"))}[entry["kind"]]
        t = op.traits(*args)
    elif entry["kind"] == "flash_decode":
        t = op.traits(x["q"], x["k"], x["v"], entry["kv_len"])
        # the benchmark also counts q and the output, which the
        # program's traits leave out
        b, kh, g, dh = (entry[k] for k in ("b", "kh", "g", "dh"))
        nbytes -= 2.0 * b * kh * g * dh * 4
    elif entry["kind"] == "spmv_bell":
        shape = (entry["rows"], entry["block_cols"] * entry["bn"])
        t = op.traits(BlockEll(x["blocks"], x["cols"], shape), x["x"])
    else:
        spec = kind.program_spec(entry)
        assert spec.num_points == load_module(
            BENCH / "counts" / "stencil.py").points(entry)
        t = op.traits(x["u"], spec, steps=entry["steps"])
    assert (flops, nbytes) == (t.work_flops, t.traffic_bytes)


def test_array_sizes_follow_streams_rule():
    # every large array is 512 MiB, four times the 128 MiB VMEM
    for entry in CONFIG["kernels"]:
        if "n" in entry:
            assert entry["n"] * 4 == 512 * 2**20
        if "grid" in entry:
            assert math.prod(entry["grid"]) * 4 == 512 * 2**20
        if entry["kind"] == "spmv_bell":
            nnzb = entry["rows"] // entry["bm"] * entry["blocks_per_row"]
            assert nnzb * entry["bm"] * entry["bn"] * 4 == 512 * 2**20


@pytest.mark.parametrize("cache_len", [129, 3200])
def test_decode_step_counts_equal_step_traits(cache_len):
    driver = load_module(BENCH / "drivers" / "lm_decode.py")
    from repro.models.advisor_map import step_traits

    lm = load_module(BENCH / "counts" / "lm.py")
    cfg = driver.model_config(LM)
    t = step_traits(cfg, 8, cache_len, dtype_bytes=2)
    assert lm.decode_step(LM, 8, cache_len, 2) == (t.work_flops,
                                                     t.traffic_bytes)


def test_flash_decode_step_is_the_attention_row():
    """At a full cache the step's attention bytes are step_traits'
    attention row plus q and the output of every layer."""
    from repro.models.advisor_map import decode_op_traits

    driver = load_module(BENCH / "drivers" / "lm_decode.py")
    lm = load_module(BENCH / "counts" / "lm.py")
    cfg = driver.model_config(LM)
    row = decode_op_traits(cfg, 8, 3200, dtype_bytes=2)["attention"]
    flops, nbytes = lm.flash_decode_step(LM, 8, 3200, 2)
    qo = 2.0 * 8 * 32 * 128 * 2 * LM["num_hidden_layers"]
    assert (flops, nbytes - qo) == (row.work_flops, row.traffic_bytes)


def test_prefill_flops_by_hand():
    lm = load_module(BENCH / "counts" / "lm.py")
    cfg = dict(LM, num_hidden_layers=1, vocab_size=256)
    d, ff, t = 4096, 11008, 2 * 8
    per_token = 4 * d * d + 3 * d * ff
    attn = 4.0 * 2 * 32 * 128 * 8 * 9 / 2
    want = 2.0 * t * per_token + attn + 5.0 * t * d * 3 + 2.0 * 2 * d * 256
    assert lm.prefill_flops(cfg, 2, 8) == pytest.approx(want)
    assert np.isfinite(want)
