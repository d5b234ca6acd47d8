"""Compile every cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python -m bench.tests.rehearse_compile [CELL ...]

For each cell of ``BENCHMARK.json`` (or those named) this lowers the
programs the window runs, at the committed sizes, for one device of a
described ``v5e:2x2`` topology, compiles them with the TPU compiler and
prints each program's ``memory_analysis``.  Kernel cells: every kernel
of the mix through the registry on the cell's engine.  LM cells: the
engine's prefill and decode step.  Nothing runs; a compile that passes
is not a chip run.
"""
from __future__ import annotations

import os
import sys

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def _report(label: str, compiled) -> None:
    m = compiled.memory_analysis()
    print(f"{label}: args {_gib(m.argument_size_in_bytes)} "
          f"out {_gib(m.output_size_in_bytes)} "
          f"temp {_gib(m.temp_size_in_bytes)} "
          f"code {_gib(m.generated_code_size_in_bytes)} "
          f"alias {_gib(m.alias_size_in_bytes)}", flush=True)


def rehearse(workload: str, device) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench import run
    from bench.common import BENCH, ROOT, load_json, load_module

    sys.path.insert(0, str(ROOT / "src"))
    one = SingleDeviceSharding(device)
    f = run.cell_spec(load_json(ROOT / "BENCHMARK.json"), workload)
    cfg, traffic = f["config"], f["traffic"]

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    if cfg["driver"] == "kernel_mix":
        from repro.kernels import registry
        dtype = jnp.dtype(cfg["dtype"])
        entries = {e["name"]: e for e in cfg["kernels"]}
        for name in traffic["order"]:
            e = entries[name]
            kind = load_module(BENCH / "kernels" / f"{e['kind']}.py")
            op = registry.get(kind.OP)
            shapes = jax.eval_shape(
                lambda k: kind.make(k, e, dtype), jax.random.key(0))
            scalar = kind.scalar(np.random.default_rng(0))
            fn = jax.jit(lambda x, e=e, kind=kind, op=op: kind.run(
                op, x, e, traffic["engine"], scalar))
            _report(f"{workload} {name}", fn.lower(sds(shapes)).compile())
        return
    driver = load_module(BENCH / "drivers" / "lm_decode.py")
    ref = load_module(f["reference"])
    from repro.models import lm
    from repro.models.engine import DecodeEngine
    b, p, g = traffic["clients"], traffic["prompt_len"], traffic["gen"]
    w = jax.eval_shape(lambda k: ref.make_weights(cfg, k), jax.random.key(0))
    params = sds(driver.program_params(w))
    eng = DecodeEngine(driver.model_config(cfg), max_batch=b, prompt_len=p,
                       max_gen=g, dtype=jnp.dtype(cfg["torch_dtype"]),
                       engine=traffic["attention_engine"], params=params)
    batch = {"tokens": jax.ShapeDtypeStruct((b, p), jnp.int32, sharding=one)}
    _report(f"{workload} prefill",
            eng._prefill.lower(params, batch).compile())
    _, caches = jax.eval_shape(eng._prefill, params, batch)
    caches = sds(jax.eval_shape(lambda c: lm.pad_caches(c, p + g), caches))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    _report(f"{workload} decode step",
            eng._step.lower(params, tok, caches, idx).compile())


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    from bench.common import ROOT, load_json

    # the program decides interpret-vs-compiled from the backend; the
    # rehearsal compiles for the chip, so it answers as a TPU would
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or [w["name"] for w in
                     load_json(ROOT / "BENCHMARK.json")["workloads"]]
    for name in names:
        rehearse(name, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
