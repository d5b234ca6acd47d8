"""Record the small profiler trace that ``test_program_trace.py`` reads.

    python -m bench.tests.record_program_trace OUT_DIR

Run on one TPU chip.  Inside one ``window`` span it makes:

* ``call:scale`` -- the Pallas scale kernel through the registry on the
  vector engine, 2^20 float32 elements: the program's
  ``dispatch.route`` and ``dispatch.launch`` spans;
* ``batch`` -- one ``DecodeEngine.generate`` of a 2-layer model (batch
  8, prompt 16, 4 tokens): ``engine.generate``, ``engine.prefill``,
  three ``engine.step`` and two ``engine.wait`` spans, the programs
  ``jit_prefill`` and ``jit_decode_step``.  Inside the first step the
  host sleeps 20 ms, inside the second it forces a garbage collection
  (``host.gc``), both with the device idle.

It copies the ``.xplane.pb`` to ``OUT_DIR/program.xplane.pb`` and writes
``OUT_DIR/program_dump.txt``, every host span and device operation of
the window, for reading the trace by hand.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileOptions, TraceAnnotation

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: {jax.devices()[0].platform}", file=sys.stderr)
        return 1
    from bench import program_trace
    from bench.trace_reduce import reduce_trace
    from repro.kernels import registry
    from repro.models import DecodeEngine, ModelConfig

    scale = registry.get("scale")
    x = jax.random.normal(jax.random.key(0), (2**20,), jnp.float32)
    cfg = ModelConfig(name="tiny-dense", family="dense", n_layers=2,
                      d_model=256, n_heads=2, n_kv_heads=2, d_ff=512,
                      vocab=1024, rope_theta=1e4)
    eng = DecodeEngine(cfg, max_batch=8, prompt_len=16, max_gen=4,
                       attention_impl="dense")
    batch = eng.make_prompt_batch()
    jax.block_until_ready(scale(x, 1.5, engine="vector"))
    np.asarray(eng.generate(batch).tokens)  # every shape of the window

    step, steps = eng.decode_step, []

    def held_step(tok, caches, i):
        steps.append(i)
        if len(steps) == 1:
            time.sleep(0.02)
        elif len(steps) == 2:
            gc.collect()
        return step(tok, caches, i)
    eng.decode_step = held_step

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp()
    with jax.profiler.trace(tmp, profiler_options=opts):
        with TraceAnnotation("window"):
            with TraceAnnotation("call:scale"):
                jax.block_until_ready(scale(x, 1.5, engine="vector"))
            with TraceAnnotation("batch"):
                np.asarray(eng.generate(batch).tokens)
    src = next(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "program.xplane.pb")
    shutil.rmtree(tmp)

    red = reduce_trace(str(out / "program.xplane.pb"),
                       ("call:", "batch", *program_trace.PREFIXES))
    window = red.window("window")
    lines = [f"window {window.start} {window.end} "
             f"offset {red.clock_offset_ns}"]
    lines += [f"span {s.name} {s.start} {s.end}" for s in red.spans]
    lines += [f"op {o.device} {o.module} {o.op} {o.span} {o.start} {o.end}"
              for o in sorted(red.ops_in(window), key=lambda o: o.start)]
    lines += [f"idle {a} {b}" for a, b in program_trace.idle(red, window)]
    (out / "program_dump.txt").write_text("\n".join(lines) + "\n")
    print("\n".join(lines[:300]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace"))
