"""``python -m benchmarks.run tune`` — search tile configs, persist winners.

Usage::

    python -m benchmarks.run tune [--kernel K] [--budget N]
        [--out tuned.json] [--size N] [--dtype D] [--seed N]
        [--time-pallas]

Per (kernel family, engine, dtype) the tuner enumerates the family's
declared ``tile_space`` (capped at ``--budget`` candidates, static
default always included), times each candidate, and records the winner
in a schema-versioned ``tuned.json`` that
``repro.core.dispatch.TuningPolicy`` consults at dispatch time.  An
existing ``--out`` file is merged (faster ``best_us`` wins per key),
so repeated partial runs accumulate.

Timing defaults to each family's pure-XLA proxy
(``repro.tuning.proxy``): real compiled wall time whose tile
sensitivity mirrors the grid launch.  ``--time-pallas`` times the
actual Pallas entry points instead — only valid on a TPU backend, where
the kernels run compiled; elsewhere they would run in the interpreter,
whose wall times measure the emulator (the cache refuses to persist
them: a tile choice laundered from them would be noise).
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from repro.core.dispatch import DEFAULT_DISPATCHER, use_interpreter
from repro.kernels import registry
from repro.tuning import (InterpretTimingError, TuningCache,
                          env_fingerprint, tune_op)

from .common import emit


def _rows_for(entry) -> dict:
    params = ";".join(f"{k}={v}" for k, v in sorted(entry.params.items()))
    delta = (entry.default_us - entry.best_us) / entry.default_us * 100 \
        if entry.default_us > 0 else 0.0
    return {
        "name": f"tune/{entry.kernel}/{entry.engine}/{entry.dtype}",
        "us_per_call": f"{entry.best_us:.1f}",
        "derived": (f"{params};default_us={entry.default_us:.1f};"
                    f"delta={delta:+.1f}%;size={entry.size};"
                    f"source={entry.source}"),
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="benchmarks.run tune",
        description=__doc__.splitlines()[0])
    p.add_argument("--kernel", default=None,
                   help="one kernel family (default: every tunable family)")
    p.add_argument("--budget", type=int, default=8,
                   help="max candidates timed per (kernel, engine, dtype) "
                        "(default 8)")
    p.add_argument("--out", default="tuned.json",
                   help="tuned cache path; an existing file is merged "
                        "(default tuned.json)")
    p.add_argument("--size", type=int, default=None,
                   help="input size to time at (default: the family's "
                        "largest bench size)")
    p.add_argument("--dtype", default=None,
                   help="restrict to one dtype (default: the family's "
                        "advertised dtypes)")
    p.add_argument("--seed", type=int, default=0,
                   help="input-builder RNG seed (default 0)")
    p.add_argument("--time-pallas", action="store_true",
                   help="time the real Pallas kernels instead of the "
                        "pure-XLA proxies (TPU backend only)")
    args = p.parse_args(argv)

    if args.time_pallas and use_interpreter():
        # statically invalid: interpret-mode Pallas wall times measure
        # the emulator, and the cache would refuse them anyway -- fail
        # before burning minutes timing candidates
        raise SystemExit(
            "error: --time-pallas needs a TPU backend: off the chip the "
            "kernels run in the interpreter, interpret-mode wall times "
            "measure the emulator's Python loop, and tile choices based "
            "on them are refused at persist. Drop --time-pallas to use "
            "the pure-XLA proxies instead.")

    if args.kernel is not None:
        try:
            ops = [registry.get(args.kernel)]
        except KeyError as exc:
            raise SystemExit(str(exc))
    else:
        ops = list(registry.all_ops())

    hw_model = DEFAULT_DISPATCHER.hw.name
    source = "pallas" if args.time_pallas else "proxy"
    # fresh results carry the environment they were timed in, so a
    # merge into an older file re-stamps the fingerprint correctly
    cache = TuningCache(fingerprint=env_fingerprint())
    rows, skipped = [], []
    for op in ops:
        if not op.tile_space:
            skipped.append(op.name)
            continue
        dtypes = (args.dtype,) if args.dtype else op.dtypes
        for engine in sorted(op.engines):
            for dtype in dtypes:
                entry = tune_op(
                    op, engine=engine, dtype=dtype, size=args.size,
                    budget=args.budget, source=source, hw_model=hw_model,
                    seed=args.seed)
                if entry is None:
                    continue
                try:
                    cache.add(entry)
                except InterpretTimingError as exc:
                    raise SystemExit(f"error: {exc}")
                rows.append(_rows_for(entry))
    if not rows:
        raise SystemExit(
            f"no tunable kernels matched (skipped: {skipped or 'none'}); "
            "families opt in by declaring a tile_space")

    if os.path.exists(args.out):
        existing = TuningCache.load_or_warn(args.out)
        existing.merge(cache)
        cache = existing
    path = cache.save(args.out)

    print("name,us_per_call,derived")
    emit(rows)
    for name in skipped:
        print(f"note: {name} declares no tile space; skipped",
              file=sys.stderr)
    print(f"wrote {path} ({len(cache)} entries)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
