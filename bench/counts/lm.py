"""FLOPs and bytes of a dense decoder's prefill and decode step, from
its published sizes (a config's JSON keys).

``decode_step`` is the per-op Eq. 2 sum of one batched single-token step
against ``cache_len`` positions: embedding row, q/k/v and o projections,
attention over the cache, SwiGLU MLP, RMSNorms and the head over the
whole vocabulary, weights and activations in ``dsize`` bytes.
"""


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return (d, h, kh, dh, cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def _matmul(batch: int, params: int, e: int, act: int = 0):
    return 2.0 * batch * params, float(params * e + batch * act * e)


def decode_step(cfg: dict, batch: int, cache_len: int, dsize: int):
    """(flops, bytes) of one decode step."""
    d, h, kh, dh, ff, v, layers = _sizes(cfg)
    qd, kvd, b, e = h * dh, kh * dh, batch, dsize
    parts = [
        (0.0, float(b * d * e)),                                  # embed
        _matmul(b, layers * d * (qd + 2 * kvd), e,
                layers * (d + qd + 2 * kvd)),                     # q, k, v
        (4.0 * b * h * cache_len * dh * layers,
         2.0 * b * cache_len * kh * dh * e * layers),             # attention
        _matmul(b, layers * qd * d, e, layers * 2 * d),           # o
        _matmul(b, layers * 3 * d * ff, e, layers * 2 * d),       # mlp
    ]
    n_norms = 1 + 2 * layers
    parts.append((5.0 * b * d * n_norms, float((2 * b * d + d) * n_norms * e)))
    parts.append(_matmul(b, v * d, e, d + v))                     # head
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def prefill_flops(cfg: dict, batch: int, prompt_len: int) -> float:
    """FLOPs a prompt pass needs: every layer over every prompt token,
    causal attention, and the head at the last position only."""
    d, h, kh, dh, ff, v, layers = _sizes(cfg)
    t = batch * prompt_len
    per_token = d * (h * dh + 2 * kh * dh) + h * dh * d + 3 * d * ff
    attn = 4.0 * batch * h * dh * prompt_len * (prompt_len + 1) / 2
    norms = 5.0 * t * d * (2 * layers + 1)
    return layers * (2.0 * t * per_token + attn) + norms + 2.0 * batch * d * v


def flash_decode_step(cfg: dict, batch: int, kv_len: int, dsize: int):
    """(flops, bytes) of one step's attention over ``kv_len`` live
    positions in every layer: the live K/V, q and the output."""
    d, h, kh, dh, ff, v, layers = _sizes(cfg)
    flops = 4.0 * batch * kh * (h // kh) * kv_len * dh
    nbytes = (2.0 * batch * kv_len * kh * dh * dsize
              + 2.0 * batch * h * dh * dsize)
    return layers * flops, layers * nbytes
