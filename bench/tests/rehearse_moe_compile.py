"""Compile the deepseek-v2-lite cell's programs for a described TPU v5e,
without a chip.

    JAX_PLATFORMS=cpu python -m bench.tests.rehearse_moe_compile

At the committed sizes (published widths, 9 layers, 8 held experts,
batch 128, prompt 256, 128 out), for one device of a described
``v5e:2x2`` topology: the engine's one-time cast, its prefill and its
decode step, each compiled by the TPU compiler with its
``memory_analysis`` printed, and the bytes the chip holds at the peak of
each program beside the driver's float32 weights (kept for the
reference) and the engine's bfloat16 copy.  Nothing runs; a compile
that passes is not a chip run.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

CELL = "deepseek-v2-lite.chat-b128"


def _gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run
    from bench.common import BENCH, ROOT, load_json, load_module

    sys.path.insert(0, str(ROOT / "src"))
    from repro.models import lm
    from repro.models.engine import DecodeEngine

    # the program decides interpret-vs-compiled from the backend; the
    # rehearsal compiles for the chip, so it answers as a TPU would
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    f = run.cell_spec(load_json(ROOT / "BENCHMARK.json"), CELL)
    cfg, traffic = f["config"], f["traffic"]
    driver = load_module(BENCH / "drivers" / "lm_moe_decode.py")
    ref = load_module(f["reference"])

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    b, p, g = traffic["clients"], traffic["prompt_len"], traffic["gen"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    w = jax.eval_shape(lambda k: ref.make_weights(cfg, k), jax.random.key(0))
    params = sds(jax.eval_shape(lambda x: driver.program_params(
        x, cfg["first_k_dense_replace"]), w))
    eng = DecodeEngine(driver.model_config(cfg), max_batch=b, prompt_len=p,
                       max_gen=g, dtype=dtype,
                       engine=traffic["attention_engine"], params=params)
    held = sds(eng.params)
    weights = nbytes(w)
    print(f"{CELL}: float32 weights {_gib(weights)}, engine's bf16 "
          f"parameters {_gib(nbytes(held))}", flush=True)

    def report(label, compiled, resident):
        m = compiled.memory_analysis()
        peak = (resident + m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{label}: args {_gib(m.argument_size_in_bytes)} "
              f"out {_gib(m.output_size_in_bytes)} "
              f"temp {_gib(m.temp_size_in_bytes)} "
              f"alias {_gib(m.alias_size_in_bytes)}; with what else the "
              f"chip holds {_gib(peak)}", flush=True)

    cast = jax.jit(lambda q: lm.compute_params(q, dtype))
    report(f"{CELL} cast", cast.lower(params).compile(), 0)
    batch = {"tokens": jax.ShapeDtypeStruct((b, p), jnp.int32, sharding=one)}
    report(f"{CELL} prefill", eng._prefill.lower(held, batch).compile(),
           weights)
    _, caches = jax.eval_shape(eng._prefill, held, batch)
    caches = sds(jax.eval_shape(lambda c: lm.pad_caches(c, p + g), caches))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    report(f"{CELL} decode step",
           eng._step.lower(held, tok, caches, idx).compile(), weights)
    return 0


if __name__ == "__main__":
    sys.exit(main())
