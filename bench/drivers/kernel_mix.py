"""Driver of a kernel-mix cell: the configuration's kernels, called one
after another through the program's registry, as a user calls them.

One iteration calls each kernel the traffic names once, in order, each
call going through ``registry.get(op)(..., engine=...)`` and ending in
``block_until_ready``.  The window repeats whole iterations until
``seconds`` have passed.  Each call's Eq. 2 bytes count toward
``kernel_gbs``.

Correctness: of every kernel, one call of the window is kept, drawn
uniformly from its calls with a generator seeded from ``--seed``
(reservoir sampling), and compared with the configuration's plain
reference after the window: the number compared is the largest absolute
error over the largest absolute reference value.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import BENCH, device_key, host_rng, load_module, round_to


class Cell:
    """One kernel-mix cell: inputs on the device, warmed calls, a window."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        self.seed = seed
        self.refs = reference.REFERENCES
        self.dtype = jnp.dtype(config["dtype"])
        entries = {e["name"]: e for e in config["kernels"]}
        self.order = [entries[name] for name in traffic["order"]]
        self.kinds = {e["kind"]: load_module(BENCH / "kernels" /
                                             f"{e['kind']}.py")
                      for e in self.order}
        self.counts = {e["name"]: load_module(BENCH / "counts" /
                                              f"{e['kind']}.py").count(
            e, self.dtype.itemsize) for e in self.order}
        self.engine = traffic["engine"]
        self.inputs: Dict[str, dict] = {}
        self.kept: Dict[str, tuple] = {}

    def setup(self) -> None:
        from repro.kernels import registry

        self.ops = {e["name"]: registry.get(self.kinds[e["kind"]].OP)
                    for e in self.order}
        order, kinds, dtype = self.order, self.kinds, self.dtype

        @jax.jit
        def make(key):
            return {e["name"]: kinds[e["kind"]].make(
                jax.random.fold_in(key, i), e, dtype)
                for i, e in enumerate(order)}

        self.inputs = jax.block_until_ready(make(device_key(self.seed)))
        warm = host_rng(self.seed, 1)
        for e in self.order:
            jax.block_until_ready(self._call(e, warm))

    def _call(self, entry, rng, scalar=None):
        kind = self.kinds[entry["kind"]]
        if scalar is None:
            scalar = kind.scalar(rng)
        out = kind.run(self.ops[entry["name"]], self.inputs[entry["name"]],
                       entry, self.engine, scalar)
        return out, scalar

    def window(self, seconds: float, annotate) -> dict:
        rng, pick = host_rng(self.seed, 2), host_rng(self.seed, 3)
        calls = {e["name"]: 0 for e in self.order}
        moved = flops = 0.0
        t0 = time.perf_counter()
        with annotate("window"):
            while True:
                for e in self.order:
                    name = e["name"]
                    with annotate(f"call:{name}"):
                        out, scalar = self._call(e, rng)
                        jax.block_until_ready(out)
                    calls[name] += 1
                    moved += self.counts[name][1]
                    flops += self.counts[name][0]
                    if pick.random() < 1.0 / calls[name]:
                        self.kept[name] = (scalar, out)
                    del out
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        n = sum(calls.values())
        return {"metrics": {"kernel_gbs": moved / elapsed / 1e9},
                "attempted": n, "failed": 0,
                "work": {"calls": {f"call:{e['name']}": self.counts[e["name"]]
                                   for e in self.order},
                         "flops": flops, "bytes": moved}}

    def release(self) -> None:
        """Nothing to free: the reference needs the inputs and the kept
        outputs, and the program holds no state of its own."""

    def check(self, control: Optional[str] = None) -> Dict[str, float]:
        """``err.<kernel>`` of every kernel's kept output; with
        ``control`` the reference computed from inputs rounded to that
        precision stands in for the program's output."""
        errs = {}
        with jax.default_matmul_precision("highest"):
            for e in self.order:
                name = e["name"]
                scalar, got = self.kept[name]
                ref = jax.jit(lambda x, s, ref=self.refs[e["kind"]], e=e:
                              ref(x, e, s))
                want = ref(self.inputs[name], scalar)
                if control is not None:
                    low = jax.tree.map(
                        lambda a: round_to(a, control)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        self.inputs[name])
                    got = ref(low, scalar)
                errs[f"err.{name}"] = _rel_err(got, want)
                del want, got
        return errs


@jax.jit
def _rel_parts(got, want):
    got = jnp.asarray(got, jnp.float32)
    return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))


def _rel_err(got, want) -> float:
    if np.shape(got) != np.shape(want):
        return float("inf")
    err, scale = _rel_parts(got, want)
    return float(err) / max(float(scale), 1e-30)
