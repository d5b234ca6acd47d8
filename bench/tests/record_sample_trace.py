"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python -m bench.tests.record_sample_trace OUT_DIR

Run on one TPU chip.  Inside one traced window it makes, each inside a
host span named as the benchmark names its own spans:

* ``call:scale`` -- the Pallas scale kernel through the registry, on
  the vector engine, 2^20 float32 elements (one ``tpu_custom_call``
  plus the wrapper's reshape/slice ops);
* a 20 ms host sleep, with nothing on the device (an idle gap);
* ``call:matmul`` -- a plain jitted 1024x1024 float32 matmul (XLA ops
  only, no Pallas).

It copies the ``.xplane.pb`` to ``OUT_DIR/sample.xplane.pb`` and writes
``OUT_DIR/sample_dump.txt``, every plane, line and the first events of
each line with their stats, for reading the trace by hand.  It also
prints what the device reports of itself (kind, memory, VMEM).
"""
from __future__ import annotations

import glob
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
    from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    from jax.experimental.pallas import tpu as pltpu
    print(f"device kind={dev.device_kind!r} count={len(jax.devices())}")
    print(f"memory_stats={dev.memory_stats()}")
    try:
        info = pltpu.get_tpu_info()
        print(f"tpu_info={info!r}")
    except Exception as e:  # noqa: BLE001 -- report what the probe saw
        print(f"tpu_info unavailable: {e!r}")

    from repro.kernels import registry
    scale = registry.get("scale")
    x = jax.random.normal(jax.random.key(0), (2**20,), jnp.float32)
    a = jax.random.normal(jax.random.key(1), (1024, 1024), jnp.float32)
    mm = jax.jit(lambda m: m @ m)
    jax.block_until_ready(scale(x, 1.5, engine="vector"))
    jax.block_until_ready(mm(a))

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp()
    with jax.profiler.trace(tmp, profiler_options=opts):
        with TraceAnnotation("call:scale"):
            jax.block_until_ready(scale(x, 1.5, engine="vector"))
        time.sleep(0.02)
        with TraceAnnotation("call:matmul"):
            jax.block_until_ready(mm(a))
    src = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "sample.xplane.pb")
    shutil.rmtree(tmp)

    lines = []
    pd = ProfileData.from_file(str(out / "sample.xplane.pb"))
    for plane in pd.planes:
        plines = list(plane.lines)
        lines.append(f"PLANE {plane.name!r} lines={len(plines)} "
                     f"stats={dict(plane.stats or {})}")
        for ln in plines:
            evs = list(ln.events)
            lines.append(f"  LINE {ln.name!r} events={len(evs)}")
            for e in evs[:12]:
                lines.append(f"    {e.name!r} start={e.start_ns} "
                             f"dur={e.duration_ns} "
                             f"stats={dict(e.stats or {})}")
    (out / "sample_dump.txt").write_text("\n".join(lines) + "\n")
    print("\n".join(lines[:400]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace"))
