"""Driver of a DeepSeek-V2 decode cell: ``lm_decode``'s closed-loop
clients, window and check, on a model with latent attention and experts.

The configuration holds the chip's share: ``n_routed_experts`` is the
experts held here (0 to n - 1, expert rank 0) and
``published.n_routed_experts`` the router's width.  The program is told
both (``ModelConfig.held_experts``); a program without that field cannot
run the cell, and set-up fails before anything is made.  Each batch's
expert load (held token-slots per layer, of the prefill and summed over
the steps) comes back with its tokens, in ``GenerationResult.moe_load``,
and goes into the window's ``work`` for the per-layer metrics.

Correctness adds ``lm.last_logit_err`` to ``lm_decode``'s gap, over the
same requests: the relative L2 error, over the vocabulary, of the
program's logits at each request's last served position against the
reference's, averaged over the requests read.  The held experts give a
few percent of a MoE layer's output (gates of a 64-wide softmax, 8
experts of 64 here); a served token's gap sees so small a change only
where two logits nearly tie, the logits themselves see it everywhere.
Each batch's last logits are copied to the host while the next batch
runs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import BENCH, device_key, load_module

LM = load_module(BENCH / "drivers" / "lm_decode.py")
COUNTS = load_module(BENCH / "counts" / "mla_moe.py")


def model_config(config: dict):
    """The program's ModelConfig for ``config``: the architecture of
    ``program_arch`` with every size and constant the configuration
    states, holding experts [0, n_routed_experts) of the published
    router width."""
    from repro.models.config import ModelConfig

    if "held_experts" not in {f.name for f in dataclasses.fields(
            ModelConfig)}:
        raise RuntimeError("the program's ModelConfig has no held_experts: "
                           "it cannot hold a share of the routed experts")
    from repro.models.config import YaRN
    for key, want in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                      ("n_group", 1), ("routed_scaling_factor", 1),
                      ("moe_layer_freq", 1), ("hidden_act", "silu")):
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the program "
                             f"implements {want!r} only")
    from repro.launch.serve import serving_configs

    _, cfg = serving_configs(config["program_arch"], smoke=False,
                             layers=config["num_hidden_layers"])
    rs = config["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r} is not yarn")
    d, h = config["hidden_size"], config["num_attention_heads"]
    cfg = dataclasses.replace(
        cfg, d_model=d, n_heads=h, n_kv_heads=config["num_key_value_heads"],
        head_dim=None, vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        rope_yarn=YaRN(factor=float(rs["factor"]),
                       original_max_position=rs[
                           "original_max_position_embeddings"],
                       beta_fast=float(rs["beta_fast"]),
                       beta_slow=float(rs["beta_slow"]),
                       mscale=float(rs["mscale"]),
                       mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        qkv_bias=config["attention_bias"],
        use_mla=True, kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"] or 0,
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=config["published"]["n_routed_experts"],
        held_experts=(0, config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        moe_d_ff=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        first_dense_layers=config["first_k_dense_replace"],
        dense_d_ff=config["intermediate_size"])
    if cfg.vocab_padded != cfg.vocab:
        raise ValueError(f"vocab {cfg.vocab} is not a whole number of the "
                         f"program's {cfg.pad_vocab_to}-row table blocks")
    return cfg


def program_params(w: dict, dense_layers: int) -> dict:
    """The reference's weights in the program's parameter layout."""
    nd = dense_layers
    attn = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")

    def stack(lo, hi):
        return {"ln1": w["ln1"][lo:hi], "ln2": w["ln2"][lo:hi],
                "attn": {k: w[k][lo:hi] for k in attn}}
    return {"embed": w["embed"], "final_norm": w["norm"], "head": w["head"],
            "first_dense": {**stack(0, nd),
                            "mlp": {k: w[k] for k in ("w_gate", "w_up",
                                                      "w_down")}},
            "layers": {**stack(nd, None),
                       "moe": {"router": w["router"], "w_gate": w["e_gate"],
                               "w_up": w["e_up"], "w_down": w["e_down"],
                               "shared": {"w_gate": w["s_gate"],
                                          "w_up": w["s_up"],
                                          "w_down": w["s_down"]}}}}


class Cell(LM.Cell):
    """``lm_decode.Cell`` with this model's counts, weights and load."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        super().__init__(config, traffic, seed, reference)
        cfg, b, p = config, self.batch, self.prompt_len
        e = self.dtype.itemsize
        steps = range(p + 1, p + self.gen)  # live lengths of the steps
        self.flops = COUNTS.prefill_flops(cfg, b, p) + sum(
            COUNTS.decode_step(cfg, b, n, e)[0] for n in steps)
        self.step_bytes = sum(COUNTS.decode_step(cfg, b, n, e)[1]
                              for n in steps)
        self.loads: list = []
        self.last_logits: list = []

    def setup(self) -> None:
        from repro.models.engine import DecodeEngine

        cfg = self.config
        program_cfg = model_config(cfg)
        make = jax.jit(lambda key: self.ref.make_weights(cfg, key))
        self.weights = jax.block_until_ready(make(device_key(self.seed)))
        self.engine = DecodeEngine(
            program_cfg, max_batch=self.batch, prompt_len=self.prompt_len,
            max_gen=self.gen, dtype=self.dtype,
            engine=self.traffic["attention_engine"],
            params=program_params(self.weights,
                                  cfg["first_k_dense_replace"]))
        self._serve(self.prompts(-1))

    def _serve(self, prompts: np.ndarray) -> np.ndarray:
        res = self.engine.generate({"tokens": jnp.asarray(prompts)},
                                   gen=self.gen)
        if self.last_logits:  # the previous batch's, copied by now
            self.last_logits[-1] = np.asarray(self.last_logits[-1])
        res.logits.copy_to_host_async()
        self.last_logits.append(res.logits)
        self.loads.append(res.moe_load)
        return np.asarray(res.tokens)

    def window(self, seconds: float, annotate) -> dict:
        self.loads, self.last_logits = [], []
        out = super().window(seconds, annotate)
        work = out["work"]
        del work["flash_decode"]
        e = self.dtype.itemsize
        calls, slots = [], 0
        for load in self.loads:
            for phase, n in (("prefill", 1), ("steps", self.gen - 1)):
                for s in np.asarray(load[phase])[:, 0]:
                    calls.append(COUNTS.expert_gmm(self.config, int(s), n, e))
                    slots += int(s)
        work["flops"] += sum(f for f, _ in calls)
        work["expert_gmm"] = calls
        work["held_slots"] = slots
        work["decode_step_bytes"] = self.step_bytes * len(self.loads)
        return out

    def check(self, control: Optional[str] = None) -> Dict[str, float]:
        """``lm.max_logit_gap`` (as ``lm_decode`` reads it) and
        ``lm.last_logit_err`` over the sampled requests; with ``control``
        the reference computed in that precision stands in for the
        program in both."""
        p = self.prompt_len
        cfg, ref = self.config, self.ref
        cast = ref.CONTROLS[control] if control else None

        @jax.jit
        def read(w, seq, served):
            want = ref.logits(cfg, w, seq, p - 1)
            last = None
            if cast is not None:
                ctl = ref.logits(cfg, w, seq, p - 1, cast)
                served, last = jnp.argmax(ctl, -1), ctl[-1]
            best = jnp.max(want, -1)
            gap = best - jnp.take_along_axis(want, served[:, None], -1)[:, 0]
            return jnp.max(gap), want[-1], last

        gaps, errs = [], []
        for k, row in self.picks():
            served = self.served[k][row]
            seq = np.concatenate([self.prompts(k)[row], served[:-1]])
            gap, want, last = read(self.weights, seq, served)
            want = np.asarray(want, np.float64)
            if last is None:
                last = np.asarray(self.last_logits[k][row])[:want.shape[0]]
            last = np.asarray(last, np.float64)
            gaps.append(float(gap))
            errs.append(np.linalg.norm(last - want) / np.linalg.norm(want))
        return {"lm.max_logit_gap": float(np.max(gaps)),
                "lm.last_logit_err": float(np.mean(errs))}
