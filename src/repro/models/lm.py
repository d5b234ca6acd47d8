"""Causal LM over every assigned family: one scan-over-layers implementation.

Layer stacks are scanned (stacked parameter pytrees) so the lowered HLO is
O(1) in depth -- essential for compiling 80-layer models against a
512-device mesh.  Hybrid models (Zamba2) scan over *super-blocks*:
`attn_every` SSM layers followed by one application of the **shared**
attention block (parameters closed over, not scanned -- the architecture's
defining weight-sharing), with per-application KV caches stacked on the
super-block axis.

Modes:
  forward/loss  -- teacher-forced training (remat per layer)
  prefill       -- full-prompt pass returning the KV/SSM caches
  decode_step   -- one token against the caches
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attention, init_attention, make_cache
from .config import ModelConfig
from .layers import Params, dense_init, init_mlp, mlp, rmsnorm
from .moe import EXPERT_LEAVES, init_moe, moe_ffn
from .ssm import init_ssm, make_ssm_state, ssm_layer

Pytree = Any


# --------------------------------------------------------------------------
# per-layer blocks
# --------------------------------------------------------------------------

def _init_dense_layer(key, cfg: ModelConfig, d_ff: Optional[int] = None,
                      cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
         "attn": init_attention(ks[0], cfg),
         "ln2": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.n_experts and not cross:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, d_ff or cfg.d_ff)
    if cross:
        p["ln_cross"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["cross"] = init_attention(ks[2], cfg, cross=True)
    return p


def _dense_block(p: Params, x, cfg: ModelConfig, *, positions, cache,
                 cache_index, enc_out=None, enc_pos=None, causal=True,
                 use_moe=None, layer=None):
    """One block.  ``layer``: the block's index in the scanned stack,
    whose routed experts ``p["moe"]`` then holds whole (see
    :func:`_split_experts`)."""
    from .attention import cross_attend

    self_cache = cache
    cross_kv = None
    if cache is not None and "ck" in cache:
        cross_kv = (cache["ck"], cache["cv"])
        self_cache = {k: v for k, v in cache.items()
                      if k in ("k", "v", "k_scale", "v_scale")}
    h, new_cache = attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                             cfg, positions=positions, cache=self_cache,
                             cache_index=cache_index, causal=causal)
    x = x + h
    aux = {}
    if "cross" in p and enc_out is not None:        # prefill/train: build kv
        h, ckv = attention(p["cross"], rmsnorm(p["ln_cross"], x,
                                               cfg.norm_eps),
                           cfg, positions=positions, kv_x=enc_out,
                           kv_positions=enc_pos)
        x = x + h
        if new_cache is not None:
            new_cache = {**new_cache, **ckv}
    elif "cross" in p and cross_kv is not None:     # decode: cached kv
        b = x.shape[0]
        kv_pos = jnp.broadcast_to(
            jnp.arange(cross_kv[0].shape[1], dtype=jnp.int32)[None],
            (b, cross_kv[0].shape[1]))
        h = cross_attend(p["cross"], rmsnorm(p["ln_cross"], x, cfg.norm_eps),
                         cfg, cross_kv,
                         positions if positions.ndim == 2 else positions[0],
                         kv_pos)
        x = x + h
        new_cache = {**new_cache, "ck": cross_kv[0], "cv": cross_kv[1]}
    moe_here = use_moe if use_moe is not None else ("moe" in p)
    if moe_here:
        h, aux = moe_ffn(p["moe"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg,
                         layer=layer)
    else:
        h = mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x + h, new_cache, aux


def _split_experts(layers: Params):
    """(the layer stack without its routed experts' weights, those
    weights whole, the layer indices); (layers, None, None) without
    experts.  A scan hands each iteration a slice of what it scans,
    which a Pallas kernel cannot read in place: every step would copy
    the held experts' weights.  Closed over whole, they are read where
    they lie, at the layer's index."""
    if "moe" not in layers:
        return layers, None, None
    moe = dict(layers["moe"])
    experts = {n: moe.pop(n) for n in EXPERT_LEAVES}
    n = experts["w_gate"].shape[0]
    return {**layers, "moe": moe}, experts, jnp.arange(n)


def _with_experts(lp: Params, experts) -> Params:
    """A scanned layer's parameters with the whole expert stack back."""
    if experts is None:
        return lp
    return {**lp, "moe": {**lp["moe"], **experts}}


def _init_ssm_layer(key, cfg: ModelConfig) -> Params:
    return {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ssm": init_ssm(key, cfg)}


def _ssm_block(p: Params, x, cfg: ModelConfig, *, state):
    h, new_state = ssm_layer(p["ssm"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                             cfg, state=state)
    return x + h, new_state


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_padded, d),
                                   jnp.float32) * 0.02,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], d, cfg.vocab_padded, scale=0.02)

    if cfg.family == "ssm":
        p["layers"] = _stack_init(lambda k: _init_ssm_layer(k, cfg), ks[2],
                                  cfg.n_layers)
    elif cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        tail = cfg.n_layers % cfg.attn_every
        p["layers"] = jax.vmap(
            lambda k: _stack_init(lambda kk: _init_ssm_layer(kk, cfg), k,
                                  cfg.attn_every)
        )(jax.random.split(ks[2], n_super))
        if tail:
            p["tail"] = _stack_init(lambda k: _init_ssm_layer(k, cfg),
                                    ks[3], tail)
        p["shared_attn"] = _init_dense_layer(ks[4], cfg)
    else:
        n_scanned = cfg.n_layers - cfg.first_dense_layers
        p["layers"] = _stack_init(lambda k: _init_dense_layer(k, cfg),
                                  ks[2], n_scanned)
        if cfg.first_dense_layers:
            p["first_dense"] = _stack_init(
                lambda k: _init_dense_layer(
                    k, dataclasses.replace(cfg, n_experts=0),
                    d_ff=cfg.dense_d_ff or cfg.d_ff),
                ks[3], cfg.first_dense_layers)
        if cfg.enc_dec:
            enc_cfg = dataclasses.replace(cfg, n_experts=0)
            p["encoder"] = _stack_init(
                lambda k: _init_dense_layer(k, enc_cfg), ks[5],
                cfg.n_enc_layers)
            p["enc_norm"] = jnp.ones((d,), jnp.float32)
            # decoder layers get cross-attention
            p["layers"] = _stack_init(
                lambda k: _init_dense_layer(k, cfg, cross=True), ks[2],
                cfg.n_layers)
    if cfg.frontend:
        p["frontend"] = {"proj": dense_init(ks[6], cfg.frontend_dim, d),
                         "bias": jnp.zeros((d,), jnp.float32)}
    return p


#: The parameter leaves every consumer casts to the compute dtype on use
#: (projections, biases, embedding, head, frontend): held already cast,
#: they give the consumers the same operands.  Every other leaf (norm
#: gains, ``a_log``, ``dt_bias``, ``d_skip``, conv weights, the MoE
#: ``router``) stays float32: ``rmsnorm``, the SSM scan and the router's
#: scores use those in float32 arithmetic.  A leaf
#: missing here costs speed only; a float32 consumer cast by mistake
#: would change the answer.
COMPUTE_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "bq", "bk", "bv",
    "wq_a", "wq_b", "wkv_a", "wkv_b",
    "w_gate", "w_up", "w_down",
    "w_z", "w_x", "w_bc", "w_dt", "out_proj",
    "embed", "head", "proj", "bias",
})


def compute_params(p: Params, dtype) -> Params:
    """``p`` with each :data:`COMPUTE_LEAVES` leaf cast to ``dtype``, the
    parameters a decode engine computing in ``dtype`` holds.  Float32
    returns ``p`` itself."""
    if jnp.dtype(dtype) == jnp.float32:
        return p
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x.astype(dtype) if path[-1].key in COMPUTE_LEAVES
                         else x), p)


def abstract_params(cfg: ModelConfig) -> Pytree:
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# --------------------------------------------------------------------------
# embedding / head
# --------------------------------------------------------------------------

def _embed_inputs(p: Params, cfg: ModelConfig, batch: Dict, dtype):
    tokens = batch["tokens"]
    x = jnp.take(p["embed"], tokens, axis=0).astype(dtype)
    if cfg.frontend == "vision":
        vis = batch["vision_embeds"].astype(dtype)            # (B,Fl,Fd)
        vis = vis @ p["frontend"]["proj"].astype(dtype) + \
            p["frontend"]["bias"].astype(dtype)
        x = jnp.concatenate([vis, x[:, cfg.frontend_len:]], axis=1)
    return x


def _logits(p: Params, cfg: ModelConfig, x):
    head = p["embed"].T if cfg.tie_embeddings else p["head"]
    return x @ head.astype(x.dtype)


def _positions(cfg: ModelConfig, batch: Dict, b: int, s: int):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.rope_kind == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, b, s))
    return pos


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------

def forward(p: Params, cfg: ModelConfig, batch: Dict, *,
            dtype=jnp.bfloat16, want_cache: bool = False, remat: bool = True,
            remat_policy: Optional[str] = None, unroll: bool = False,
            act_spec=None, return_hidden: bool = False):
    """Full-sequence pass.  Returns (logits, caches|None, aux).
    unroll=True unrolls layer scans (dry-run collective accounting).
    act_spec: PartitionSpec pinned onto the residual stream after every
    block -- P(dp, None, None) forces the FSDP (weight-gathered) layout,
    P(dp, 'model', None) forces sequence-parallel residency.
    return_hidden: skip the LM head (chunked-loss path computes it)."""
    x = _embed_inputs(p, cfg, batch, dtype)

    def pin(h):
        if act_spec is None:
            return h
        return jax.lax.with_sharding_constraint(h, act_spec)
    x = pin(x)
    b, s, _ = x.shape
    positions = _positions(cfg, batch, b, s)
    aux_sum = {"aux_loss": jnp.zeros((), jnp.float32),
               "z_loss": jnp.zeros((), jnp.float32)}

    enc_out = enc_pos = None
    if cfg.enc_dec:
        enc_out, enc_pos = _encode(p, cfg, batch, dtype, unroll=unroll)

    def maybe_remat(fn):
        if not remat:
            return fn
        policy = None
        if remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)

    caches = {}
    if cfg.family == "ssm":
        def body(carry, layer_p):
            h, st = _ssm_block(layer_p, carry, cfg, state=None)
            return pin(h), st
        x, states = jax.lax.scan(maybe_remat(body), x, p["layers"],
                                 unroll=unroll)
        caches["ssm"] = states
    elif cfg.family == "hybrid":
        def super_body(carry, layer_p):
            def inner(c, lp):
                h, st = _ssm_block(lp, c, cfg, state=None)
                return h, st
            h, states = jax.lax.scan(inner, carry, layer_p, unroll=unroll)
            h, att_cache, _ = _dense_block(
                p["shared_attn"], h, cfg, positions=positions, cache=None,
                cache_index=None)
            return pin(h), (states, att_cache)
        x, (states, att_caches) = jax.lax.scan(maybe_remat(super_body), x,
                                               p["layers"], unroll=unroll)
        caches["ssm"], caches["attn"] = states, att_caches
        if "tail" in p:
            def tail_body(carry, lp):
                h, st = _ssm_block(lp, carry, cfg, state=None)
                return h, st
            x, tail_states = jax.lax.scan(maybe_remat(tail_body), x,
                                          p["tail"], unroll=unroll)
            caches["tail"] = tail_states
    else:
        if "first_dense" in p:
            def fd_body(carry, lp):
                h, kv, _ = _dense_block(lp, carry, cfg, positions=positions,
                                        cache=None, cache_index=None,
                                        use_moe=False)
                return h, kv
            x, fd_caches = jax.lax.scan(maybe_remat(fd_body), x,
                                        p["first_dense"], unroll=unroll)
            caches["first_dense"] = fd_caches

        scanned, experts, index = _split_experts(p["layers"])

        def body(carry, xs):
            (h, a), (layer_p, i) = carry, xs
            h, kv, aux = _dense_block(_with_experts(layer_p, experts), h,
                                      cfg, positions=positions, cache=None,
                                      cache_index=None, enc_out=enc_out,
                                      enc_pos=enc_pos, layer=i)
            for k2 in a:
                a = dict(a, **{k2: a[k2] + aux.get(k2, 0.0)})
            return (pin(h), a), (kv, aux.get("load"))
        (x, aux_sum), (kv_caches, loads) = jax.lax.scan(
            maybe_remat(body), (x, aux_sum), (scanned, index), unroll=unroll)
        caches["attn"] = kv_caches
        if loads is not None:
            caches["moe_load"] = {"prefill": loads,
                                  "steps": jnp.zeros_like(loads)}

    x = rmsnorm(p["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, (caches if want_cache else None), aux_sum
    logits = _logits(p, cfg, x)
    return logits, (caches if want_cache else None), aux_sum


def _encode(p: Params, cfg: ModelConfig, batch: Dict, dtype,
            unroll: bool = False):
    frames = batch["enc_frames"].astype(dtype)               # (B,Se,Fd)
    h = frames @ p["frontend"]["proj"].astype(dtype) + \
        p["frontend"]["bias"].astype(dtype)
    b, se, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(se, dtype=jnp.int32)[None], (b, se))

    def body(carry, lp):
        x, kv, _ = _dense_block(lp, carry, cfg, positions=pos, cache=None,
                                cache_index=None, causal=False, use_moe=False)
        return x, None
    h, _ = jax.lax.scan(jax.checkpoint(body), h, p["encoder"],
                        unroll=unroll)
    return rmsnorm(p["enc_norm"], h, cfg.norm_eps), pos


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def _nll(logits, labels, vocab):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    # one-hot contraction (gather-free: TPU/GSPMD friendly on sharded vocab)
    gold = jnp.einsum("bsv,bsv->bs", logits.astype(jnp.float32),
                      jax.nn.one_hot(labels, vocab, dtype=jnp.float32))
    return lse - gold


def loss_fn(p: Params, cfg: ModelConfig, batch: Dict, *, dtype=jnp.bfloat16,
            remat_policy: Optional[str] = None, unroll: bool = False,
            act_spec=None, loss_chunks: int = 0, remat: bool = True):
    """loss_chunks > 0 streams the LM head + softmax over sequence chunks
    so the (B, S, V) logits tensor never materializes (memory-term
    optimization; see EXPERIMENTS.md §Perf)."""
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if loss_chunks:
        hidden, _, aux = forward(p, cfg, batch, dtype=dtype, remat=remat,
                                 remat_policy=remat_policy, unroll=unroll,
                                 act_spec=act_spec, return_hidden=True)
        b, s, d = hidden.shape
        assert s % loss_chunks == 0, (s, loss_chunks)
        c = s // loss_chunks
        if mask is None:
            mask = jnp.ones((b, s), jnp.float32)
        head = (p["embed"].T if cfg.tie_embeddings else p["head"])
        head = head.astype(hidden.dtype)
        xs = (hidden.reshape(b, loss_chunks, c, d).swapaxes(0, 1),
              labels.reshape(b, loss_chunks, c).swapaxes(0, 1),
              mask.astype(jnp.float32).reshape(
                  b, loss_chunks, c).swapaxes(0, 1))

        def body(carry, xsc):
            tot, cnt = carry
            hc, lc, mc = xsc
            nll_c = _nll(hc @ head, lc, cfg.vocab_padded)
            return (tot + (nll_c * mc).sum(), cnt + mc.sum()), None

        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            xs)
        nll_mean = tot / jnp.maximum(cnt, 1.0)
        loss = nll_mean + aux["aux_loss"] + aux["z_loss"]
        return loss, {"loss": loss, "nll": nll_mean, **aux}
    logits, _, aux = forward(p, cfg, batch, dtype=dtype, remat=remat,
                             remat_policy=remat_policy, unroll=unroll,
                             act_spec=act_spec)
    nll = _nll(logits, labels, cfg.vocab_padded)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = loss + aux["aux_loss"] + aux["z_loss"]
    metrics = {"loss": loss, "nll": (nll * mask).sum() / jnp.maximum(
        mask.sum(), 1.0), **aux}
    return loss, metrics


# --------------------------------------------------------------------------
# caches / decode
# --------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16, enc_len: Optional[int] = None) -> Dict:
    def stack(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None],
                                                       (n, *x.shape)), tree)
    if cfg.family == "ssm":
        return {"ssm": stack(make_ssm_state(cfg, batch), cfg.n_layers)}
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        tail = cfg.n_layers % cfg.attn_every
        c = {"ssm": stack(stack(make_ssm_state(cfg, batch), cfg.attn_every),
                          n_super),
             "attn": stack(make_cache(cfg, batch, max_len, dtype), n_super)}
        if tail:
            c["tail"] = stack(make_ssm_state(cfg, batch), tail)
        return c
    base = make_cache(cfg, batch, max_len, dtype)
    if cfg.enc_dec:
        se = enc_len or max_len
        base = {**base,
                "ck": jnp.zeros((batch, se, cfg.n_kv_heads, cfg.head_dim),
                                dtype),
                "cv": jnp.zeros((batch, se, cfg.n_kv_heads, cfg.head_dim),
                                dtype)}
    n_scanned = cfg.n_layers - cfg.first_dense_layers
    c = {"attn": stack(base, n_scanned)}
    if cfg.n_experts:
        zeros = jnp.zeros((n_scanned, 2), jnp.int32)
        c["moe_load"] = {"prefill": zeros, "steps": zeros}
    if cfg.first_dense_layers:
        c["first_dense"] = stack(make_cache(cfg, batch, max_len, dtype),
                                 cfg.first_dense_layers)
    return c


def pad_caches(caches: Dict, max_len: int) -> Dict:
    """Grow prefill caches (seq = prompt len) to the serving max_len."""
    def pad(path, x):
        names = [p.key for p in path if hasattr(p, "key")]
        if names and names[-1] in ("k", "v", "latent", "k_rope"):
            axis = x.ndim - (3 if names[-1] in ("latent", "k_rope") else 4) + 1
            pad_amt = max_len - x.shape[axis]
            if pad_amt > 0:
                widths = [(0, 0)] * x.ndim
                widths[axis] = (0, pad_amt)
                return jnp.pad(x, widths)
        return x
    return jax.tree_util.tree_map_with_path(pad, caches)


def decode_step(p: Params, cfg: ModelConfig, tokens, caches: Dict,
                cache_index, *, dtype=jnp.bfloat16,
                batch_extras: Optional[Dict] = None, unroll: bool = False):
    """One decode step.  tokens: (B,1); cache_index: scalar int32.
    Enc-dec cross K/V comes from the caches (filled by prefill)."""
    x = jnp.take(p["embed"], tokens, axis=0).astype(dtype)
    b = tokens.shape[0]
    pos = jnp.full((b, 1), cache_index, jnp.int32)
    if cfg.rope_kind == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, b, 1))

    new_caches = dict(caches)
    if cfg.family == "ssm":
        def body(carry, xs):
            lp, st = xs
            h, st2 = _ssm_block(lp, carry, cfg, state=st)
            return h, st2
        x, states = jax.lax.scan(body, x, (p["layers"], caches["ssm"]),
                                 unroll=unroll)
        new_caches["ssm"] = states
    elif cfg.family == "hybrid":
        def super_body(carry, xs):
            lp, st, kv = xs
            def inner(c, ys):
                ilp, ist = ys
                h, ist2 = _ssm_block(ilp, c, cfg, state=ist)
                return h, ist2
            h, st2 = jax.lax.scan(inner, carry, (lp, st), unroll=unroll)
            h, kv2, _ = _dense_block(p["shared_attn"], h, cfg, positions=pos,
                                     cache=kv, cache_index=cache_index)
            return h, (st2, kv2)
        x, (states, kvs) = jax.lax.scan(
            super_body, x, (p["layers"], caches["ssm"], caches["attn"]),
            unroll=unroll)
        new_caches["ssm"], new_caches["attn"] = states, kvs
        if "tail" in p:
            def tail_body(carry, xs):
                lp, st = xs
                h, st2 = _ssm_block(lp, carry, cfg, state=st)
                return h, st2
            x, ts = jax.lax.scan(tail_body, x, (p["tail"], caches["tail"]),
                                 unroll=unroll)
            new_caches["tail"] = ts
    else:
        if "first_dense" in p:
            def fd_body(carry, xs):
                lp, kv = xs
                h, kv2, _ = _dense_block(lp, carry, cfg, positions=pos,
                                         cache=kv, cache_index=cache_index,
                                         use_moe=False)
                return h, kv2
            x, fd = jax.lax.scan(fd_body, x,
                                 (p["first_dense"], caches["first_dense"]),
                                 unroll=unroll)
            new_caches["first_dense"] = fd

        scanned, experts, index = _split_experts(p["layers"])

        def body(carry, xs):
            lp, kv, i = xs
            h, kv2, aux = _dense_block(_with_experts(lp, experts), carry, cfg,
                                       positions=pos, cache=kv,
                                       cache_index=cache_index, layer=i)
            return h, (kv2, aux.get("load"))
        x, (kvs, loads) = jax.lax.scan(body, x,
                                       (scanned, caches["attn"], index),
                                       unroll=unroll)
        new_caches["attn"] = kvs
        if loads is not None and "moe_load" in caches:
            steps = caches["moe_load"]["steps"]
            new_caches["moe_load"] = {
                **caches["moe_load"],
                "steps": jnp.stack([steps[:, 0] + loads[:, 0],
                                    jnp.maximum(steps[:, 1], loads[:, 1])],
                                   axis=1)}

    x = rmsnorm(p["final_norm"], x, cfg.norm_eps)
    return _logits(p, cfg, x), new_caches


def prefill(p: Params, cfg: ModelConfig, batch: Dict, *, dtype=jnp.bfloat16,
            unroll: bool = False):
    """Prompt pass: returns last-position logits + caches (KV in bf16).
    The head runs at the last position only."""
    hidden, caches, _ = forward(p, cfg, batch, dtype=dtype, want_cache=True,
                                remat=False, unroll=unroll,
                                return_hidden=True)
    return _logits(p, cfg, hidden[:, -1:]), caches
