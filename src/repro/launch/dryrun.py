import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x cell x mesh) and
extract the roofline terms (deliverable e + g).

For each cell the matching step function is jitted with production
in/out shardings against abstract inputs (ShapeDtypeStruct only -- no
allocation), compiled, and the compiled artifact is mined for:
  * memory_analysis()  -> bytes/device (proves the config fits)
  * cost_analysis()    -> HLO FLOPs / bytes (per-device)
  * as_text()          -> collective bytes by op kind
Rows append to a JSON cache so the 40-cell sweep is resumable.

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --cell train_4k
  python -m repro.launch.dryrun --all [--multipod] [--out runs/dryrun.json]
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_arch
from ..core import TPU_V5E, collective_stats
from ..core.jaxpr_cost import program_cost
from ..models import lm
from ..obs.log import LOG
from ..optim.adamw import AdamW
from ..sharding import rules
from . import steps
from .cells import CELLS, applicable
from .mesh import make_production_mesh


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _lower_one(cfg, cell, *, multi_pod: bool = False,
               opts: dict | None = None):
    """Lower+compile one (cfg, cell); returns (compiled, step, args)."""
    opts = opts or {}
    dp = ("pod", "data") if multi_pod else "data"

    mesh = make_production_mesh(multi_pod=multi_pod)
    params_abs = lm.abstract_params(cfg)
    p_specs = rules.param_pspecs(params_abs, mesh)
    if opts.get("zero1"):
        opt_specs = rules.zero1_pspecs(params_abs, mesh)
    else:
        opt_specs = p_specs
    vocab_ok = cfg.vocab_padded % mesh.shape["model"] == 0
    vspec = "model" if vocab_ok else None

    with jax.set_mesh(mesh):
        if cell.kind == "train":
            bf16_params = opts.get("params_dtype") == "bf16"
            if bf16_params:
                params_abs = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
                    if a.dtype == jnp.float32 else a, params_abs)
            opt = AdamW(master_weights=bf16_params)
            opt_state_abs = jax.eval_shape(opt.init, params_abs)
            batch_abs = steps.input_specs(cfg, cell)
            b_specs = rules.input_pspecs(cfg, mesh, "train")
            act_spec = None
            if opts.get("layout") == "fsdp":
                # ZeRO-3: params sharded over the flattened mesh, batch
                # sharded over every axis, weights gathered per layer
                fs_axes = tuple(mesh.axis_names)
                p_specs = rules.fsdp_pspecs(params_abs, mesh)
                opt_specs = p_specs
                act_spec = P(fs_axes, None, None)
                b_specs = {k: P(fs_axes, *([None] * (len(v.shape) - 1)))
                           for k, v in batch_abs.items()}
            elif opts.get("layout") == "sp":
                act_spec = P(dp, "model", None)
            step = steps.make_train_step(
                cfg, opt, remat_policy=opts.get("remat_policy"),
                grad_compress=opts.get("grad_compress"),
                unroll=opts.get("unroll", False), act_spec=act_spec,
                loss_chunks=opts.get("loss_chunks", 0),
                cast_params=opts.get("cast_params", False),
                remat=not opts.get("no_remat", False))
            in_sh = (_named(mesh, p_specs),
                     steps.AdamWState(NamedSharding(mesh, P()),
                                      _named(mesh, opt_specs),
                                      _named(mesh, opt_specs),
                                      _named(mesh, opt_specs)
                                      if bf16_params else None),
                     _named(mesh, b_specs))
            out_sh = (in_sh[0], in_sh[1], NamedSharding(mesh, P()))
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=out_sh,
                              donate_argnums=(0, 1)).lower(
                params_abs, opt_state_abs, batch_abs)
            args = (params_abs, opt_state_abs, batch_abs)
        elif cell.kind == "prefill":
            batch_abs = steps.input_specs(cfg, cell)
            b_specs = rules.input_pspecs(cfg, mesh, "prefill")
            caches_abs = jax.eval_shape(
                lambda: lm.init_caches(cfg, cell.global_batch, cell.seq))
            c_specs = rules.cache_pspecs(cfg, mesh, caches_abs)
            step = steps.make_prefill_step(cfg, unroll=opts.get("unroll", False))
            out_sh = (NamedSharding(mesh, P(dp, None, vspec)),
                      _named(mesh, c_specs))
            lowered = jax.jit(step,
                              in_shardings=(_named(mesh, p_specs),
                                            _named(mesh, b_specs)),
                              out_shardings=out_sh).lower(
                params_abs, batch_abs)
            args = (params_abs, batch_abs)
        else:  # decode
            seq_shard = cell.global_batch == 1
            kv_dtype = {"int8": jnp.int8, "bf16": jnp.bfloat16}[
                opts.get("kv_dtype", "bf16")]
            tok_abs, caches_abs, idx_abs = steps.decode_input_specs(
                cfg, cell, cache_dtype=kv_dtype)
            if opts.get("params_dtype") == "bf16":
                # serve from bf16 weights (halves weight reads + residency)
                params_abs = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, jnp.bfloat16)
                    if a.dtype == jnp.float32 else a, params_abs)
            c_specs = rules.cache_pspecs(cfg, mesh, caches_abs,
                                         seq_shard=seq_shard)
            tok_spec = P(None, None) if seq_shard else P(dp, None)
            step = steps.make_decode_step(cfg, unroll=opts.get("unroll", False))
            c_sh = _named(mesh, c_specs)
            lowered = jax.jit(
                step,
                in_shardings=(_named(mesh, p_specs),
                              NamedSharding(mesh, tok_spec), c_sh,
                              NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(
                    mesh, P(None, None, vspec) if seq_shard
                    else P(dp, None, vspec)), c_sh),
                donate_argnums=(2,)).lower(
                params_abs, tok_abs, caches_abs, idx_abs)
            args = (params_abs, tok_abs, caches_abs, idx_abs)
        compiled = lowered.compile()
    return compiled, step, args


def _depth_variants(cfg):
    """Two shallow configs + (L1, L2, L_full) in 'scan units' for linear
    extrapolation of per-device collective bytes over depth."""
    if cfg.family == "hybrid":
        tail = cfg.n_layers % cfg.attn_every
        mk = lambda s: dataclasses.replace(
            cfg, n_layers=cfg.attn_every * s + tail)
        return mk(1), 1, mk(2), 2, cfg.n_layers // cfg.attn_every
    fd = min(cfg.first_dense_layers, 1)

    def mk(n):
        kw = dict(n_layers=n, first_dense_layers=fd)
        if cfg.enc_dec:
            kw["n_enc_layers"] = n
        return dataclasses.replace(cfg, **kw)
    return mk(2), 2, mk(4), 4, cfg.n_layers


def _extrapolate(d1, l1, d2, l2, lf):
    out = {}
    for k in d1:
        slope = (d2[k] - d1[k]) / (l2 - l1)
        out[k] = max(0.0, d1[k] + slope * (lf - l1))
    return out


def lower_cell(arch: str, cell_name: str, *, multi_pod: bool = False,
               opts: dict | None = None, skip_variants: bool = False):
    """Full dry-run for one cell: compile + roofline terms (deliverable g).

    FLOPs/bytes come from the jaxpr walker (exact scan accounting; XLA's
    cost_analysis ignores loop trip counts -- tests/test_analysis.py).
    Collective bytes come from the partitioned HLO, extrapolated linearly
    from two shallow-depth compiles (collectives inside the layer scan are
    printed once).  memory_analysis comes from the full-depth artifact.
    """
    opts = opts or {}
    cfg = get_arch(arch)
    cell = CELLS[cell_name]
    ok, reason = applicable(cfg, cell)
    if not ok:
        return None, None, {"skipped": reason}

    t0 = time.time()
    compiled, step, args = _lower_one(cfg, cell, multi_pod=multi_pod,
                                      opts=opts)
    t1 = time.time()
    with jax.set_mesh(make_production_mesh(multi_pod=multi_pod)):
        jc = program_cost(step, *args)      # global analytic cost
    chips = 512 if multi_pod else 256
    hw = TPU_V5E

    coll_full_once = collective_stats(compiled.as_text())
    if skip_variants:
        coll = dict(coll_full_once.bytes_by_kind)
        coll_counts = dict(coll_full_once.count_by_kind)
    else:
        cfg1, l1, cfg2, l2, lf = _depth_variants(cfg)
        vopts = dict(opts, unroll=True)   # unrolled: in-loop collectives visible
        c1, s1, a1 = _lower_one(cfg1, cell, multi_pod=multi_pod, opts=vopts)
        c2, s2, a2 = _lower_one(cfg2, cell, multi_pod=multi_pod, opts=vopts)
        st1, st2 = (collective_stats(c1.as_text()),
                    collective_stats(c2.as_text()))
        coll = _extrapolate(st1.bytes_by_kind, l1, st2.bytes_by_kind, l2, lf)
        coll_counts = _extrapolate(st1.count_by_kind, l1,
                                   st2.count_by_kind, l2, lf)
    coll_per_dev = sum(coll.values())

    mem = compiled.memory_analysis()
    t_compute = jc["flops"] / (chips * hw.matrix.peak_flops)
    t_memory = jc["bytes"] / (chips * hw.mem_bw)
    t_collective = coll_per_dev / hw.link_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)
    mf = steps.model_flops(cfg, cell)
    t_bound = max(terms.values())
    xla_cost = compiled.cost_analysis()

    meta = {
        "arch": arch, "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "lower_compile_s": round(t1 - t0, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "total_gb": round((mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes) / 2**30, 3),
        },
        "hlo_flops": jc["flops"], "dot_flops": jc["dot_flops"],
        "hlo_bytes": jc["bytes"],
        "coll_bytes_per_dev": coll_per_dev,
        "collectives": {"bytes_by_kind": coll,
                        "count_by_kind": coll_counts},
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_collective, "dominant": dominant,
        "t_bound_s": t_bound,
        "model_flops": mf,
        "useful_ratio": mf / jc["flops"] if jc["flops"] else None,
        "mfu_bound": (mf / (t_bound * chips * hw.matrix.peak_flops)
                      if t_bound else None),
        "xla_cost_flops_per_dev_loops_once": xla_cost.get("flops"),
        "opts": opts,
    }
    return compiled, step, meta


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--cell", choices=sorted(CELLS), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--grad-compress", default=None)
    ap.add_argument("--layout", default=None, choices=(None, "fsdp", "sp"))
    ap.add_argument("--loss-chunks", type=int, default=0)
    ap.add_argument("--kv-dtype", default=None, choices=(None, "int8", "bf16"))
    ap.add_argument("--cast-params", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--params-dtype", default=None, choices=(None, "bf16"))
    ap.add_argument("--tag", default=None, help="label for this opts combo")
    args = ap.parse_args()
    LOG.configure(level="info")   # launcher mains narrate by default

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    rows = {}
    if out.exists():
        rows = {f"{r['arch']}/{r['cell']}/{r['mesh']}"
                + (f"/{r['tag']}" if r.get("tag") else ""): r
                for r in json.loads(out.read_text())}

    pairs = ([(args.arch, args.cell)] if not args.all else
             [(a, c) for a in sorted(ARCHS) for c in sorted(CELLS)])
    meshes = [False, True] if args.both_meshes else [args.multipod]
    opts = {k: getattr(args, k.replace("-", "_")) for k in
            ("zero1", "remat_policy", "grad_compress", "layout",
             "loss_chunks", "kv_dtype", "cast_params",
             "params_dtype", "no_remat") if getattr(
                args, k.replace("-", "_"))}

    tag = f"/{args.tag}" if args.tag else ""
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        for arch, cell in pairs:
            key = f"{arch}/{cell}/{mesh_name}{tag}"
            if key in rows and not args.force:
                LOG.info("skip-cached", cell=key)
                continue
            LOG.info("lower+compile", cell=key)
            try:
                # multi-pod rows prove compile+fit; roofline variants are
                # derived on the single-pod mesh only (spec: §Roofline)
                _, _, meta = lower_cell(arch, cell, multi_pod=multi_pod,
                                        opts=opts,
                                        skip_variants=multi_pod)
            except Exception as e:  # a failure here is a sharding bug
                traceback.print_exc()
                LOG.error("lower+compile failed", cell=key,
                          error=f"{type(e).__name__}: {e}")
                meta = {"arch": arch, "cell": cell, "mesh": mesh_name,
                        "tag": args.tag,
                        "error": f"{type(e).__name__}: {e}"}
                rows[key] = meta
                out.write_text(json.dumps(list(rows.values()), indent=1,
                                          default=str))
                continue
            meta["tag"] = args.tag
            if "skipped" in meta:
                meta = {"arch": arch, "cell": cell, "mesh": mesh_name,
                        "tag": args.tag, "skipped": meta["skipped"]}
                LOG.info("cell skipped", cell=key,
                         reason=meta["skipped"])
            else:
                LOG.info(
                    "cell ok", cell=key,
                    gib_per_dev=meta["bytes_per_device"]["total_gb"],
                    dominant=meta["dominant"],
                    t_bound_s=round(max(meta["t_compute_s"],
                                        meta["t_memory_s"],
                                        meta["t_collective_s"]), 4),
                    compile_s=meta["lower_compile_s"])
            rows[key] = meta
            out.write_text(json.dumps(list(rows.values()), indent=1,
                                      default=str))
    print(f"wrote {out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
