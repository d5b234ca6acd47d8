"""Decode engine: scan-over-layers prefill/decode with per-phase timing.

The executable half of the model-scale verdict
(``repro.models.advisor_map``): one :class:`DecodeEngine` owns a
config's parameters and the two jitted entry points — ``prefill`` (full
prompt pass, caches built once) and ``decode_step`` (one token against
the KV/SSM caches through ``repro.models.lm``'s single ``lax.scan`` over
the stacked layer block).  Attention inside the scan is
registry-dispatched by default (``decode_attention_impl='registry'``):
every layer's cache scan goes through the registered flash-decode
``EngineOp``, so the §6 Advice that classifies the decode step is
exercised by the very kernel that serves it, and the engine
('vector'|'matrix'|'auto') is a constructor flag — the serving sweep's
A/B lever.

``generate`` runs greedy decode and reports the prefill/decode wall
split plus the per-step mean the ``model_verdict`` claim anchors to.
Its host work is in :mod:`repro.obs` spans, which a profiler trace
shows as ``engine.generate`` (the call), ``engine.prefill`` and
``engine.step`` (each program's enqueue and its argmax) and
``engine.wait`` (each ``block_until_ready``); the two programs are
``jit_prefill`` and ``jit_decode_step``.  A model with routed experts
carries its load in the caches (``moe_load``: per MoE layer, the held
token-slots and the most one held expert took, of the prefill and
summed or maxed over the steps), summed on the device and read once
after the last wait, inside ``engine.moe_load`` (stats ``held_slots``,
``max_expert_slots``, ``dropped``).  Construction opens
``engine.cast_params`` (stats ``leaves`` cast, ``bytes_before`` and
``bytes_after`` of the parameters), around the program
``jit_cast_params`` where there is a leaf to cast.
``cache_state``/``load_cache_state`` expose the KV caches as a plain
pytree for ``repro.runtime.checkpoint`` round-trips.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..data.synthetic import make_batch
from ..obs.trace import TRACER
from . import lm
from .advisor_map import ModelVerdict, model_verdict, step_traits
from .config import ModelConfig

__all__ = ["DecodeEngine", "GenerationResult"]


@dataclasses.dataclass(frozen=True)
class GenerationResult:
    """One greedy generation: tokens + the phase-split timings."""

    tokens: jnp.ndarray        # (B, gen) greedy tokens (incl. first)
    logits: jnp.ndarray        # (B, vocab_padded) last-step logits
    caches: Any                # final KV/SSM caches (checkpointable)
    prefill_s: float           # prompt-pass wall time
    decode_s: float            # all decode steps' wall time
    decode_steps: int          # steps timed inside decode_s
    # per MoE layer [held token-slots, most slots one held expert took]
    # of the prefill and of the steps (summed; maxed): the caches'
    # ``moe_load``, on the host; None without routed experts
    moe_load: Optional[Dict[str, Any]] = None

    @property
    def per_step_s(self) -> float:
        """Mean decode-step wall time (0 for single-token generations)."""
        if self.decode_steps == 0:
            return 0.0
        return self.decode_s / self.decode_steps


def _nbytes(leaves) -> int:
    return sum(x.size * x.dtype.itemsize for x in leaves)


def _cast_params(params: Any, dtype) -> Any:
    """``lm.compute_params(params, dtype)``, computed once by the program
    ``jit_cast_params`` inside the span ``engine.cast_params``.  Shapes
    alone (``jax.ShapeDtypeStruct`` leaves, a compile rehearsal) give
    the cast shapes."""
    def cast_params(p):
        return lm.compute_params(p, dtype)

    shapes = jax.eval_shape(cast_params, params)
    before, after = jax.tree.leaves(params), jax.tree.leaves(shapes)
    leaves = sum(a.dtype != b.dtype for a, b in zip(before, after))
    with TRACER.span("cast_params", layer="engine", leaves=leaves,
                     bytes_before=_nbytes(before),
                     bytes_after=_nbytes(after)):
        if not leaves:
            return params
        if isinstance(before[0], jax.ShapeDtypeStruct):
            return shapes
        return jax.jit(cast_params)(params)


def _moe_load(caches: Any) -> Optional[Dict[str, Any]]:
    """The caches' expert load on the host, recorded as the stats of the
    span ``engine.moe_load``; None for a model without routed experts.
    ``dropped`` is 0: the expert layer has no capacity to drop at."""
    if not isinstance(caches, dict) or "moe_load" not in caches:
        return None
    load = jax.device_get(caches["moe_load"])
    slots = int(load["prefill"][:, 0].sum() + load["steps"][:, 0].sum())
    most = int(max(load["prefill"][:, 1].max(), load["steps"][:, 1].max()))
    with TRACER.span("moe_load", layer="engine", held_slots=slots,
                     max_expert_slots=most, dropped=0):
        return load


class DecodeEngine:
    """Prefill + scan-over-layers greedy decode for one ModelConfig.

    ``params`` (given, or drawn from ``seed``) are held as
    ``lm.compute_params`` gives them for ``dtype``, cast once here: the
    projections, biases, embedding, head and frontend in ``dtype``, the
    norm gains and SSM terms in float32.  So no prefill or step casts a
    weight; a float32 engine holds the very arrays it was given.

    The layer stack is *scanned*, not unrolled (``lm.decode_step``'s
    single ``lax.scan`` over the stacked parameter pytree), so compiled
    size is O(1) in depth; ``unroll=True`` flips to the unrolled
    reference graph the correctness tier diffs against.
    """

    def __init__(self, cfg: ModelConfig, *, max_batch: int = 4,
                 prompt_len: int = 16, max_gen: int = 16,
                 dtype=jnp.float32, seed: int = 0, engine: str = "auto",
                 attention_impl: str = "registry", unroll: bool = False,
                 params: Optional[Any] = None):
        self.cfg = dataclasses.replace(
            cfg, decode_attention_impl=attention_impl,
            decode_attention_engine=engine)
        self.engine = engine
        self.max_batch = max_batch
        self.prompt_len = prompt_len
        self.max_gen = max_gen
        self.dtype = dtype
        self.params = _cast_params(
            params if params is not None
            else lm.init_params(self.cfg, jax.random.key(seed)), dtype)
        cfg_ = self.cfg

        # named functions, so the programs are named after them
        def prefill(p, b):
            return lm.prefill(p, cfg_, b, dtype=dtype, unroll=unroll)

        def decode_step(p, t, c, i):
            return lm.decode_step(p, cfg_, t, c, i, dtype=dtype,
                                  unroll=unroll)

        self._prefill = jax.jit(prefill)
        self._step = jax.jit(decode_step)
        TRACER.watch_gc()

    # -- core phases -------------------------------------------------------

    @property
    def max_len(self) -> int:
        """The serving cache length every decode step attends over."""
        return self.prompt_len + self.max_gen

    def make_prompt_batch(self, batch: Optional[int] = None,
                          seed: int = 0) -> Dict:
        """A capacity-sized synthetic prompt batch (compiled-shape reuse)."""
        return make_batch(self.cfg, batch or self.max_batch,
                          self.prompt_len, seed=seed)

    def prefill(self, batch: Dict) -> Tuple[jnp.ndarray, Any]:
        """Prompt pass: last-position logits + caches padded to max_len."""
        logits, caches = self._prefill(self.params, batch)
        return logits, lm.pad_caches(caches, self.max_len)

    def decode_step(self, tokens, caches, index: int
                    ) -> Tuple[jnp.ndarray, Any]:
        """One token for every sequence: (B,1) tokens → (B,1,V) logits."""
        return self._step(self.params, tokens, caches, jnp.int32(index))

    def lower_step(self, tokens, caches, index: int):
        """The decode step lowered for these arguments (HLO inspection:
        which kernels the step really launches)."""
        return self._step.lower(self.params, tokens, caches,
                                jnp.int32(index))

    # -- greedy generation -------------------------------------------------

    def generate(self, batch: Dict, gen: Optional[int] = None,
                 ) -> GenerationResult:
        """Greedy decode ``gen`` tokens with a prefill/decode wall split.

        The decode phase times ``gen - 1`` steps (the first token falls
        out of prefill's last-position logits); ``block_until_ready``
        fences both phases so the split is honest about async dispatch.
        """
        gen = min(self.max_gen, gen or self.max_gen)
        with TRACER.span("generate", layer="engine"):
            t0 = time.perf_counter()
            with TRACER.span("prefill", layer="engine"):
                logits, caches = self.prefill(batch)
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            with TRACER.span("wait", layer="engine"):
                jax.block_until_ready(tok)
            t1 = time.perf_counter()
            toks = [tok]
            steps = 0
            for i in range(self.prompt_len, self.prompt_len + gen - 1):
                with TRACER.span("step", layer="engine"):
                    logits, caches = self.decode_step(tok, caches, i)
                    tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
                toks.append(tok)
                steps += 1
            with TRACER.span("wait", layer="engine"):
                jax.block_until_ready(tok)
            t2 = time.perf_counter()
            load = _moe_load(caches)
            return GenerationResult(
                tokens=jnp.concatenate(toks, axis=1),
                logits=logits[:, -1] if logits.ndim == 3 else logits,
                caches=caches, prefill_s=t1 - t0, decode_s=t2 - t1,
                decode_steps=steps, moe_load=load)

    def warmup(self, batch: Optional[Dict] = None) -> None:
        """Compile prefill + step outside any timed region."""
        self.generate(batch if batch is not None
                      else self.make_prompt_batch(), gen=2)

    # -- checkpointable cache state ---------------------------------------

    @staticmethod
    def cache_state(caches: Any) -> Dict:
        """The KV/SSM caches as a plain dict pytree for checkpointing."""
        return jax.tree.map(lambda x: x, caches)

    def load_cache_state(self, template: Any, state: Dict) -> Any:
        """Re-adopt a restored cache pytree (shape/dtype-checked)."""
        flat_t, tdef = jax.tree.flatten(template)
        flat_s, sdef = jax.tree.flatten(state)
        if tdef != sdef:
            raise ValueError(f"cache structure mismatch: {tdef} vs {sdef}")
        for a, b in zip(flat_t, flat_s):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(
                    f"cache leaf mismatch: {a.shape}/{a.dtype} vs "
                    f"{b.shape}/{b.dtype}")
        return jax.tree.unflatten(tdef, flat_s)

    # -- analytics ---------------------------------------------------------

    def verdict(self, cfg: Optional[ModelConfig] = None) -> ModelVerdict:
        """The per-op model-scale verdict at this engine's (B, S, dtype).

        ``cfg`` defaults to the engine's own config; the serving path
        passes the *full-size* architecture so the verdict speaks at
        model scale while execution stays smoke-sized.
        """
        return model_verdict(cfg or self.cfg, self.max_batch, self.max_len,
                             dtype_bytes=jnp.dtype(self.dtype).itemsize)

    def traits(self, cfg: Optional[ModelConfig] = None):
        """Whole-step Eq. 2 traits (the record's analytic join fields)."""
        return step_traits(cfg or self.cfg, self.max_batch, self.max_len,
                           dtype_bytes=jnp.dtype(self.dtype).itemsize)
