"""Driver of an LM decode cell: closed-loop clients served through the
program's ``DecodeEngine.generate`` (the call ``LMDecodeExecutor.execute``
makes), on the wall clock.

``clients`` closed-loop clients each send a request of ``prompt_len``
seeded random tokens and wait for its ``gen`` greedy tokens.  They send
together, so every call serves a batch of ``clients`` requests; a new
batch of prompts is drawn for every call.  A request's latency runs
from when its batch was handed to ``generate`` to when its tokens are
back on the host.  The window runs whole batches until ``seconds`` have
passed.  ``repro.serving``'s scheduler is not on this path: its clock is
virtual.

Correctness: one finished request in every batch row, each from a batch
drawn from the seed, is run through the plain float32 reference over
its prompt and served tokens; the number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import BENCH, device_key, host_rng, load_module, percentile

COUNTS = load_module(BENCH / "counts" / "lm.py")


def model_config(config: dict):
    """The program's ModelConfig for ``config``: the architecture from
    ``repro.launch.serve.serving_configs`` at the stated depth, every
    size and constant set as the configuration states it."""
    from repro.launch.serve import serving_configs

    _, cfg = serving_configs(config["program_arch"], smoke=False,
                             layers=config["num_hidden_layers"])
    d, h = config["hidden_size"], config["num_attention_heads"]
    cfg = dataclasses.replace(
        cfg, d_model=d, n_heads=h, n_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or d // h,
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        qkv_bias=config["attention_bias"])
    if cfg.vocab_padded != cfg.vocab:
        raise ValueError(f"vocab {cfg.vocab} is not a whole number of the "
                         f"program's {cfg.pad_vocab_to}-row table blocks")
    return cfg


def program_params(w: dict) -> dict:
    """The reference's weights in the program's parameter layout (the
    same arrays, renamed)."""
    return {"embed": w["embed"], "final_norm": w["norm"], "head": w["head"],
            "layers": {"ln1": w["ln1"], "ln2": w["ln2"],
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                       "mlp": {k: w[k] for k in ("w_gate", "w_up",
                                                 "w_down")}}}


class Cell:
    """One LM decode cell: weights, a warmed engine, a window of batches."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.ref = reference
        self.batch = traffic["clients"]
        self.prompt_len, self.gen = traffic["prompt_len"], traffic["gen"]
        self.dtype = jnp.dtype(config["torch_dtype"])
        self.served: list = []
        cfg, b, p = config, self.batch, self.prompt_len
        e = self.dtype.itemsize
        steps = range(p + 1, p + self.gen)  # live lengths of the steps
        self.flops = COUNTS.prefill_flops(cfg, b, p) + sum(
            COUNTS.decode_step(cfg, b, n, e)[0] for n in steps)
        flash = [COUNTS.flash_decode_step(cfg, b, n, e) for n in steps]
        self.flash = (sum(f[0] for f in flash), sum(f[1] for f in flash))

    def prompts(self, k: int) -> np.ndarray:
        """The prompts of batch ``k`` (batch -1 warms up)."""
        rng = host_rng(self.seed, 10, k + 1)
        return rng.integers(0, self.config["vocab_size"],
                            (self.batch, self.prompt_len), dtype=np.int32)

    def setup(self) -> None:
        from repro.models.engine import DecodeEngine

        cfg = self.config
        make = jax.jit(lambda key: self.ref.make_weights(cfg, key))
        self.weights = jax.block_until_ready(make(device_key(self.seed)))
        self.engine = DecodeEngine(
            model_config(cfg), max_batch=self.batch,
            prompt_len=self.prompt_len, max_gen=self.gen, dtype=self.dtype,
            engine=self.traffic["attention_engine"],
            params=program_params(self.weights))
        self._serve(self.prompts(-1))

    def _serve(self, prompts: np.ndarray) -> np.ndarray:
        res = self.engine.generate({"tokens": jnp.asarray(prompts)},
                                   gen=self.gen)
        return np.asarray(res.tokens)

    def window(self, seconds: float, annotate) -> dict:
        latencies = []
        t0 = time.perf_counter()
        with annotate("window"):
            while True:
                prompts = self.prompts(len(self.served))
                sent = time.perf_counter()
                with annotate("batch"):
                    tokens = self._serve(prompts)
                done = time.perf_counter()
                self.served.append(tokens)
                latencies += [done - sent] * self.batch
                if done - t0 >= seconds:
                    break
        elapsed = done - t0
        n = len(latencies)
        batches = latencies[::self.batch]
        print("batch_s " + " ".join(f"{x:.4f}" for x in batches),
              file=sys.stderr)
        return {"metrics": {
                    "lm_tokens_per_s": n * self.gen / elapsed,
                    "lm_request_p95_s": percentile(latencies, 95)},
                "attempted": n, "failed": 0,
                "work": {"batches": len(self.served),
                         "flops": self.flops * len(self.served),
                         "flash_decode": self.flash}}

    def release(self) -> None:
        """Free the engine (its jitted programs and anything they hold);
        the weights stay for the reference."""
        self.engine = None

    def picks(self) -> list:
        """The requests the check reads: one in every batch row, each
        from a batch drawn from the seed, so a fault confined to one row
        of the batch is always read.  Every request is as long as the
        longest."""
        batches = host_rng(self.seed, 4).integers(0, len(self.served),
                                                  self.batch)
        return [(int(k), row) for row, k in enumerate(batches)]

    def check(self, control: Optional[str] = None) -> Dict[str, float]:
        """``lm.max_logit_gap`` over the sampled requests; with
        ``control`` the token that the reference computed in that
        precision puts first stands in for each served token."""
        p = self.prompt_len
        cfg, ref = self.config, self.ref
        cast = ref.CONTROLS[control] if control else None

        @jax.jit
        def gaps(w, seq, served):
            want = ref.logits(cfg, w, seq, p - 1)
            if cast is not None:
                served = jnp.argmax(ref.logits(cfg, w, seq, p - 1, cast), -1)
            best = jnp.max(want, -1)
            return best - jnp.take_along_axis(want, served[:, None], -1)[:, 0]

        worst = []
        for k, row in self.picks():
            served = self.served[k][row]
            seq = np.concatenate([self.prompts(k)[row], served[:-1]])
            worst.append(float(jnp.max(gaps(self.weights, seq, served))))
        return {"lm.max_logit_gap": float(np.max(worst))}
