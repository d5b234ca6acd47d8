#!/usr/bin/env python3
"""Smoke test of the paper's main path, compiled, on TPU chips.

    python chip_smoke.py              # one chip: device, kernels, serve
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Phases, each printing its own lines:

* **device** -- the platform must be ``tpu`` and its ``device_kind``
  must be in ``repro.core.hw.DEVICE_KINDS``; the dispatcher's hardware
  model must be that spec, and no Pallas launch may take the
  interpreter.
* **kernels** -- every registered kernel x {vector, matrix} x declared
  dtype at a real size, called through ``registry.get(name)(...,
  engine=...)`` and compared with the op's reference.
* **serve** -- deepseek-7b at its published widths, cut to
  ``SERVE_LAYERS`` layers (the only cut), serves a few requests through
  ``run_session`` with the ``repro.launch.serve`` executor; then the
  Pallas flash-decode path is compared step by step with the dense XLA
  attention path on the same tokens.
* **mesh** (``--chips 4`` only) -- ``MeshExecutor(4)`` on scale (data
  split) and the 2d5pt stencil (row blocks with a ``ppermute`` halo),
  each compared with the one-device reference.

Every time printed is a smoke wall -- one warmed call ending in
``block_until_ready`` -- not a benchmark number.  Data and weights are
random, made from ``--seed``.  Any failure exits non-zero before the
last line, which is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.dispatch import DEFAULT_DISPATCHER, use_interpreter  # noqa
from repro.core.hw import spec_for_device_kind  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.kernels.stencil.defs import TABLE3_DEPTH, suite  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: rtol = atol per dtype, as tests/test_registry.py (f32) and
#: tests/test_flash_decode.py (bf16) hold the kernels to.
TOL = {"float32": 1e-4, "bfloat16": 2e-2}
ELEMENTWISE_N = 2**27            # elements per array: 512 MiB in f32
ATTN_SHAPE = dict(b=4, kh=32, g=1, dh=128, s=4096)  # deepseek-7b decode
SPMV_ROWS = 8192
STENCIL_SHAPE = {2: (4096, 4096), 3: (256, 256, 256)}  # 64 MiB in f32
SERVE_ARCH, SERVE_LAYERS = "deepseek-7b", 4
SERVE_BATCH, SERVE_PROMPT, SERVE_GEN, SERVE_REQUESTS = 4, 128, 32, 8
#: max |registry - dense| over max |dense|, prefill and every step
LOGIT_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    """A phase found a wrong result or a wrong device."""


def _warmed_wall(fn):
    """(output, seconds) of the second call; the first one compiles."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _compare(got, want, dtype: str):
    """(max abs error, within rtol = atol = TOL[dtype])."""
    tol = TOL[dtype]
    g = jnp.asarray(got, jnp.float32)
    w = jnp.asarray(want, jnp.float32)
    if g.shape != w.shape:
        raise SmokeFailure(f"shape {g.shape} != reference {w.shape}")
    err = jnp.abs(g - w)
    return float(err.max()), bool(jnp.all(err <= tol + tol * jnp.abs(w)))


def phase_device(chips: int) -> jax.Device:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX found {len(devices)} {dev.platform} device(s); "
            f"this smoke test runs only on TPU chips")
    spec = spec_for_device_kind(dev.device_kind)
    if DEFAULT_DISPATCHER.hw is not spec:
        raise SmokeFailure(f"dispatcher models {DEFAULT_DISPATCHER.hw.name}"
                           f" but the device is {spec.name}")
    if use_interpreter():
        raise SmokeFailure("Pallas launches would take the interpreter")
    if len(devices) < chips:
        raise SmokeFailure(f"{chips} chips asked, {len(devices)} found")
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} spec={spec.name} "
          f"hbm={spec.mem_bw / 1e9:.0f} GB/s interpret=False")
    return dev


def kernel_cases(seed: int):
    """(label, op, args, kwargs, dtype) for every kernel at a real size."""
    rng = np.random.default_rng(seed)
    key = jax.random.key(seed)
    for name in ("scale", "triad", "axpy"):
        op = registry.get(name)
        for dtype in op.dtypes:
            args, kw = op.make_inputs(rng, ELEMENTWISE_N, dtype)
            yield f"{name}[n={ELEMENTWISE_N}]", op, args, kw, dtype
    op = registry.get("attention")
    a = ATTN_SHAPE
    for dtype in op.dtypes:
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, 1), 3)
        q = jax.random.normal(kq, (a["b"], a["kh"], a["g"], a["dh"]), dtype)
        kvs = (a["b"], a["s"], a["kh"], a["dh"])
        k = jax.random.normal(kk, kvs, dtype)
        v = jax.random.normal(kv, kvs, dtype)
        shape = ",".join(f"{dim}={n}" for dim, n in a.items())
        yield (f"attention[{shape}]", op,
               (q, k, v, a["s"] - a["s"] // 8), {}, dtype)
    op = registry.get("spmv")
    args, kw = op.make_inputs(rng, SPMV_ROWS, "float32")
    yield f"spmv[rows={SPMV_ROWS}]", op, args, kw, "float32"
    op = registry.get("stencil")
    for i, (name, spec) in enumerate(sorted(suite().items())):
        shape = STENCIL_SHAPE[spec.ndim]
        u = jax.random.normal(jax.random.fold_in(key, 10 + i), shape)
        steps = TABLE3_DEPTH[name]
        yield (f"stencil/{name}[{'x'.join(map(str, shape))},t={steps}]",
               op, (u, spec), {"steps": steps}, "float32")


def phase_kernels(seed: int) -> None:
    seen, failed = set(), []
    for label, op, args, kw, dtype in kernel_cases(seed):
        seen.add(op.name)
        with jax.default_matmul_precision("highest"):
            want = op.reference(*args, **kw)
        for engine in ("vector", "matrix"):
            out, wall = _warmed_wall(
                lambda: op(*args, engine=engine, **kw))
            err, ok = _compare(out, want, dtype)
            print(f"[kernels] {label} {engine} {dtype} max_err={err:.3e} "
                  f"tol={TOL[dtype]:g} {'ok' if ok else 'FAIL'} "
                  f"smoke_wall_ms={wall * 1e3:.3f}", flush=True)
            if not ok:
                failed.append(f"{label}/{engine}/{dtype}")
        del out, want, args
    missing = set(registry.names()) - seen
    if missing:
        raise SmokeFailure(f"kernels with no smoke case: {sorted(missing)}")
    if failed:
        raise SmokeFailure(f"kernels off their reference: {failed}")


def compare_attention_paths(reg, dense, batch, gen: int):
    """Registry (Pallas flash-decode) vs dense XLA attention, same tokens.

    Both engines prefill the same batch, then decode the registry path's
    greedy tokens for ``gen - 1`` steps, under HIGHEST matmul precision
    (on a TPU the default f32 precision of XLA and of the kernel
    differ).  Returns (relative logit error of the prefill and of each
    step, greedy agreement, top-2 margins of the dense logits where the
    greedy tokens differ).
    """
    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs, agree, margins = [], [], []

    def step(lr, ld):
        errs.append(rel(lr, ld))
        tr, td = jnp.argmax(lr, -1), jnp.argmax(ld, -1)
        same = np.asarray(tr == td)
        agree.extend(same.tolist())
        top2 = np.asarray(jax.lax.top_k(ld, 2)[0])
        margins.extend(float(t[0] - t[1]) for t, s in zip(top2, same)
                       if not s)
        return tr[:, None]

    with jax.default_matmul_precision("highest"):
        (lr, cr), (ld, cd) = reg.prefill(batch), dense.prefill(batch)
        tok = step(lr[:, -1], ld[:, -1])
        for i in range(reg.prompt_len, reg.prompt_len + gen - 1):
            lr, cr = reg.decode_step(tok, cr, i)
            ld, cd = dense.decode_step(tok, cd, i)
            tok = step(lr[:, 0], ld[:, 0])
    return errs, float(np.mean(agree)), margins


def phase_serve(seed: int) -> None:
    from repro.launch import serve
    from repro.models.engine import DecodeEngine
    from repro.serving import (BatchPolicy, LM_DECODE, Request, SLO,
                               SessionConfig, format_summary, run_session)
    from repro.serving.loadgen import TraceLoadGen

    full, cfg = serve.serving_configs(SERVE_ARCH, smoke=False,
                                      layers=SERVE_LAYERS)
    print(f"[serve] {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}"
          f" kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} layers={cfg.n_layers} of "
          f"{full.n_layers} (the only cut) params="
          f"{cfg.param_count() / 1e9:.2f}B f32")
    print(f"[serve] advisor {serve.decode_advice(full)}")
    ex = serve.make_executor(cfg, full, batch=SERVE_BATCH,
                             prompt_len=SERVE_PROMPT, gen=SERVE_GEN,
                             seed=seed)
    reqs = [Request(rid=i, kernel=LM_DECODE, arrival_s=0.01 * i,
                    size=SERVE_GEN) for i in range(SERVE_REQUESTS)]
    session = SessionConfig(
        kernel=LM_DECODE, workload="trace", duration_s=1.0,
        size=SERVE_GEN, seed=seed,
        policy=BatchPolicy(max_batch=SERVE_BATCH, max_wait_s=0.05),
        slo=SLO(latency_ms=1000.0))
    t0 = time.perf_counter()
    _, summary, _ = run_session(session, ex,
                                source=TraceLoadGen(requests=reqs))
    wall = time.perf_counter() - t0
    for line in format_summary(summary):
        print(f"[serve] {line}")
    print(f"[serve] session smoke_wall_s={wall:.2f} (compile included)")
    if summary.completed != SERVE_REQUESTS:
        raise SmokeFailure(f"served {summary.completed} of "
                           f"{SERVE_REQUESTS} requests")

    eng = ex.engine
    batch = eng.make_prompt_batch(seed=seed)
    logits, caches = eng.prefill(batch)
    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    hlo = eng.lower_step(tok, caches, SERVE_PROMPT).as_text()
    if "tpu_custom_call" not in hlo:
        raise SmokeFailure("the decode step launches no compiled Pallas "
                           "kernel")
    dense = DecodeEngine(cfg, max_batch=SERVE_BATCH,
                         prompt_len=SERVE_PROMPT, max_gen=SERVE_GEN,
                         dtype=eng.dtype, attention_impl="dense",
                         params=eng.params)
    errs, agreement, margins = compare_attention_paths(eng, dense, batch,
                                                       SERVE_GEN)
    ok = max(errs) <= LOGIT_RTOL
    print(f"[serve] pallas-vs-dense logits: prefill rel_err={errs[0]:.3e} "
          f"last-step rel_err={errs[-1]:.3e} worst={max(errs):.3e} "
          f"tol={LOGIT_RTOL:g} {'ok' if ok else 'FAIL'}")
    print(f"[serve] greedy token agreement {agreement:.4f} over "
          f"{SERVE_BATCH * SERVE_GEN} tokens; top-2 margins where they "
          f"differ: {[f'{m:.3e}' for m in margins] or 'none'}")
    if not ok:
        raise SmokeFailure("Pallas and dense attention logits disagree")


def phase_mesh(seed: int, chips: int) -> None:
    from repro.sharding import MeshExecutor

    mex = MeshExecutor(chips)
    rng = np.random.default_rng(seed)
    scale = registry.get("scale")
    args, kw = scale.make_inputs(rng, ELEMENTWISE_N, "float32")
    cases = [(f"scale[n={ELEMENTWISE_N}]", scale, args, kw)]
    stencil = registry.get("stencil")
    u = jax.random.normal(jax.random.key(seed), STENCIL_SHAPE[2])
    steps = TABLE3_DEPTH["2d5pt"]
    cases.append((f"stencil/2d5pt[{'x'.join(map(str, u.shape))},t={steps}]",
                  stencil, (u, suite()["2d5pt"]), {"steps": steps}))
    for label, op, args, kw in cases:
        run = mex.run(op, *args, **kw)
        on = run.out_sharding.device_set
        if run.devices != chips or len(on) != chips or \
                {d.platform for d in on} != {"tpu"}:
            raise SmokeFailure(f"{label}: mesh program ran on {len(on)} "
                               f"device(s) ({run.devices} shards)")
        with jax.default_matmul_precision("highest"):
            want = op.reference(*args, **kw)
        err, ok = _compare(run.out, want, "float32")
        print(f"[mesh] {label} kind={run.plan.spec.kind} shards="
              f"{run.devices} output_on={len(on)} tpu devices "
              f"max_err={err:.3e} tol={TOL['float32']:g} "
              f"{'ok' if ok else 'FAIL'} smoke_wall_ms={run.wall_s * 1e3:.3f}",
              flush=True)
        if not ok:
            raise SmokeFailure(f"{label}: mesh output off the reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase (and only it)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print(f"[cache] {enable_compile_cache()}")
    dev = phase_device(args.chips)
    if args.chips == 1:
        phase_kernels(args.seed)
        phase_serve(args.seed)
    else:
        phase_mesh(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
