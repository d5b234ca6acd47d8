"""FLOPs and bytes of a DeepSeek-V2 decoder's share on one chip (MLA
attention, a leading dense layer, MoE layers with held and shared
experts), from its configuration's JSON keys.

The routed experts' part depends on the routing, so it is counted per
held token-slot: ``expert_gmm`` gives one layer's three projections over
``slots`` slots.  Everything else is fixed by the shapes:
``prefill_flops`` (the head at the last position only) and
``decode_step``, whose bytes are the weights a step reads (the held
experts' whole, the router in float32, the rest in ``dsize`` bytes) and
the live latent cache.  Decode attention is the absorbed form the
program runs: q folded into the latent, scores against the cached
latent and rotary key, the output unfolded through ``kv_b``.
"""


def _sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            cfg["published"]["n_routed_experts"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_hidden_layers"],
            cfg["first_k_dense_replace"])


def _weights(cfg: dict):
    """Parameters a token's pass reads, by part (no routed experts)."""
    d, h, nope, rope, vd, r, ff, fe, fs, e, held, v, n, nd = _sizes(cfg)
    mla = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) \
        + h * vd * d
    return {"mla": n * mla, "dense": nd * 3 * d * ff,
            "shared": (n - nd) * 3 * d * fs, "router": (n - nd) * d * e,
            "head": d * v}


def expert_gmm(cfg: dict, slots: float, calls: int, dsize: int):
    """(flops, bytes) of ``calls`` calls of one MoE layer's held-expert
    products (gate, up, down) over ``slots`` token-slots in all: 6 d f
    FLOPs a slot; each call reads every held expert's weights once, and
    each slot's rows in and out."""
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["n_routed_experts"]
    flops = 6.0 * d * fe * slots
    nbytes = 3.0 * (calls * held * d * fe + slots * (d + fe)) * dsize
    return flops, nbytes


def prefill_flops(cfg: dict, batch: int, prompt_len: int) -> float:
    """FLOPs of a prompt pass without the routed experts: every layer
    over every prompt token (MLA decompressed, causal attention), the
    router and shared experts, norms, the head at the last position."""
    d, h, nope, rope, vd, r, ff, fe, fs, e, held, v, n, nd = _sizes(cfg)
    w = _weights(cfg)
    t = batch * prompt_len
    per_token = w["mla"] + w["dense"] + w["shared"] + w["router"]
    attn = 2.0 * batch * h * (nope + rope + vd) * prompt_len * (
        prompt_len + 1) / 2
    norms = 5.0 * t * (d * (2 * n + 1) + r * n)
    return 2.0 * t * per_token + n * attn + norms + 2.0 * batch * d * v


def decode_step(cfg: dict, batch: int, cache_len: int, dsize: int):
    """(flops, bytes) of one decode step against ``cache_len`` live
    positions, without the routed experts' FLOPs (``expert_gmm``) but
    with their weights among the bytes."""
    d, h, nope, rope, vd, r, ff, fe, fs, e, held, v, n, nd = _sizes(cfg)
    w = _weights(cfg)
    b = batch
    absorbed = 2.0 * b * h * (nope * r + r * vd)             # fold, unfold
    scores = 2.0 * b * h * cache_len * (2 * r + rope)        # qK, pV
    flops = (2.0 * b * (sum(w.values())) + n * (absorbed + scores)
             + 5.0 * b * (d * (2 * n + 1) + r * n))
    experts = (n - nd) * held * 3 * d * fe
    nbytes = ((w["mla"] + w["dense"] + w["shared"] + w["head"] + experts)
              * dsize + w["router"] * 4
              + n * b * cache_len * (r + rope) * dsize)      # latent cache
    return flops, float(nbytes)
