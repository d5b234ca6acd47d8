"""Fault tolerance: atomic checkpoints, bit-exact restart, stragglers.

The restart drill is the core: train 10 steps straight vs. crash at step
6 + resume -- final parameters must be *bit-identical* (the data pipeline
replays deterministically from the step counter).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, reduced
from repro.data.pipeline import TokenPipeline
from repro.models import lm
from repro.optim.adamw import AdamW
from repro.runtime import checkpoint as ckpt
from repro.runtime.train_loop import (FailureInjector, StragglerWatchdog,
                                      TrainLoopConfig, run)


@pytest.fixture()
def setup(tmp_path):
    cfg = reduced(get_arch("deepseek-7b"))
    opt = AdamW(lr=1e-3, clip_norm=1.0)
    pipe = TokenPipeline(cfg, global_batch=4, seq=32)

    def init_state():
        params = lm.init_params(cfg, jax.random.key(0))
        return params, opt.init(params)

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch, dtype=jnp.float32),
            has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, metrics

    return cfg, init_state, step_fn, pipe, tmp_path


def _leaves_equal(a, b) -> bool:
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


def test_restart_is_bit_exact(setup):
    cfg, init_state, step_fn, pipe, tmp = setup
    lc = TrainLoopConfig(total_steps=10, ckpt_every=3, log_every=100,
                         ckpt_dir=str(tmp / "a"), async_ckpt=False)
    p_straight, o_straight, _ = run(lc, init_state=init_state,
                                    step_fn=step_fn, batch_fn=pipe.batch,
                                    log=lambda *_: None)

    lc2 = TrainLoopConfig(total_steps=10, ckpt_every=3, log_every=100,
                          ckpt_dir=str(tmp / "b"), async_ckpt=False)
    with pytest.raises(RuntimeError, match="injected failure"):
        run(lc2, init_state=init_state, step_fn=step_fn,
            batch_fn=pipe.batch, injector=FailureInjector(fail_at_step=7),
            log=lambda *_: None)
    assert ckpt.latest_step(tmp / "b") == 6   # last complete checkpoint
    # resume: run() picks up from the checkpoint automatically
    p_resumed, o_resumed, _ = run(lc2, init_state=init_state,
                                  step_fn=step_fn, batch_fn=pipe.batch,
                                  log=lambda *_: None)
    assert _leaves_equal(p_straight, p_resumed)
    assert _leaves_equal(o_straight.m, o_resumed.m)


def test_checkpoint_atomicity(tmp_path):
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
    ckpt.save(tmp_path, 5, tree)
    ckpt.save(tmp_path, 10, tree)
    assert ckpt.latest_step(tmp_path) == 10
    # a .tmp directory must never be visible as a checkpoint
    assert not list(tmp_path.glob("*.tmp"))
    restored = ckpt.restore(tmp_path, tree, step=5)
    assert np.array_equal(np.asarray(restored["a"]), np.arange(8.0))


def test_async_checkpointer(tmp_path):
    tree = {"w": jnp.full((4, 4), 3.0)}
    w = ckpt.AsyncCheckpointer(tmp_path)
    w.save(1, tree)
    w.save(2, jax.tree.map(lambda x: x * 2, tree))  # waits for save 1
    w.wait()
    assert ckpt.latest_step(tmp_path) == 2
    r = ckpt.restore(tmp_path, tree)
    assert float(np.asarray(r["w"])[0, 0]) == 6.0


def test_prune_old(tmp_path):
    tree = {"x": jnp.zeros(2)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, tree)
    ckpt.prune_old(tmp_path, keep=2)
    steps = sorted(int(p.name.split("_")[-1])
                   for p in tmp_path.glob("step_*"))
    assert steps == [4, 5]


def test_straggler_watchdog():
    wd = StragglerWatchdog(threshold=2.0, warmup=2)
    for step, dt in enumerate([0.1, 0.1, 0.1, 0.1, 0.5, 0.1]):
        wd.observe(step, dt)
    assert len(wd.flagged) == 1
    assert wd.flagged[0][0] == 4
    # ewma not poisoned by the spike
    assert wd.ewma < 0.2


def test_engine_kv_cache_checkpoint_roundtrip(tmp_path):
    """A decode interrupted mid-generation resumes bit-exactly.

    The serving engine's KV caches checkpoint through
    ``runtime/checkpoint`` as a plain pytree: prefill + one decode
    step, save, restore into a *fresh* engine (same params), and the
    remaining steps must produce identical logits to the uninterrupted
    run.
    """
    from repro.models import DecodeEngine
    cfg = reduced(get_arch("deepseek-7b"))
    eng = DecodeEngine(cfg, max_batch=2, prompt_len=4, max_gen=4,
                       dtype=jnp.float32, seed=0)
    batch = eng.make_prompt_batch(seed=1)
    logits, caches = eng.prefill(batch)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    lg, caches = eng.decode_step(tok, caches, 4)
    tok = jnp.argmax(lg[:, 0], axis=-1)[:, None]
    ckpt.save(tmp_path, 1, eng.cache_state(caches))

    eng2 = DecodeEngine(cfg, max_batch=2, prompt_len=4, max_gen=4,
                        dtype=jnp.float32, params=eng.params)
    template = jax.tree.map(jnp.zeros_like, eng2.cache_state(caches))
    caches2 = eng2.load_cache_state(template,
                                    ckpt.restore(tmp_path, template, step=1))
    assert _leaves_equal(caches, caches2)
    lg1, _ = eng.decode_step(tok, caches, 5)
    lg2, _ = eng2.decode_step(tok, caches2, 5)
    assert np.array_equal(np.asarray(lg1), np.asarray(lg2))


def test_engine_cache_restore_rejects_mismatched_state(tmp_path):
    """A checkpoint from a different serving shape must be refused, not
    silently adopted (shape/dtype validation on every leaf)."""
    from repro.models import DecodeEngine
    cfg = reduced(get_arch("deepseek-7b"))
    eng = DecodeEngine(cfg, max_batch=2, prompt_len=4, max_gen=4,
                       dtype=jnp.float32, seed=0)
    _, caches = eng.prefill(eng.make_prompt_batch())
    good = eng.cache_state(caches)
    bad = jax.tree.map(lambda x: jnp.zeros(x.shape[:-1] + (x.shape[-1] + 1,),
                                           x.dtype), good)
    with pytest.raises(ValueError, match="cache leaf mismatch"):
        eng.load_cache_state(good, bad)


# --------------------------------------------------------------------------
# ROADMAP item 5: elastic serving runtime (repro.serving.elastic)
# --------------------------------------------------------------------------
# Formerly three strict xfails; the integration landed, so these now
# drive the real paths: resize under queue pressure, mid-batch shard
# failure + re-dispatch, and scheduler/tuner/engine-cache restart.

def _elastic_cfg(**overrides):
    """A small, fast serving config for the elastic drills."""
    from repro.serving import BatchPolicy, SLO, SessionConfig
    kw = dict(kernel="scale", workload="bursty", engine="vector",
              rate_rps=64.0, duration_s=0.5, size=4096, dtype="float32",
              seed=0, policy=BatchPolicy(max_batch=4, max_wait_s=0.01),
              slo=SLO(latency_ms=50.0), num_shards=1)
    kw.update(overrides)
    return SessionConfig(**kw)


def test_serving_session_resizes_mesh_under_load():
    """Queue-depth pressure grows the mesh; idle traffic shrinks it —
    and every re-shard is bit-exact (the served results' checksum
    matches the fault-free fixed-width replay exactly)."""
    from repro.serving import ElasticSession
    cfg = _elastic_cfg(rate_rps=256.0)
    session = ElasticSession(cfg, min_shards=1, max_shards=4,
                             grow_depth=4, idle_shrink_s=0.05,
                             resize_cooldown_s=0.02)
    _, summary, record = session.run()
    events = record["events"]
    resizes = [e for e in events["log"] if e.get("kind") == "resize"
               and not e.get("skipped")]
    assert any(e["reason"] == "queue-pressure" for e in resizes), resizes
    assert all(e["reshard_exact"] for e in resizes)
    assert all(e["to"] != e["from"] for e in resizes)
    # elasticity must not corrupt a single result: bit-exact vs. the
    # fault-free (fixed-width) replay of the same seeded traffic
    assert events["checksum"] == events["fault_free"]["checksum"]
    assert summary.completed == summary.offered


def test_shard_failure_redispatch_mid_batch():
    """An injected shard death mid-batch is recovered by re-dispatching
    the dead shard's ShardPlan ranges: same bits, bounded recovery
    latency, no dropped requests."""
    from repro.serving import ChaosInjector, ElasticSession, session
    # the seam run_session callers import still exists
    assert hasattr(session, "redispatch_failed_shard")
    cfg = _elastic_cfg(num_shards=2)
    sess = ElasticSession(cfg, injector=ChaosInjector("fail@0.05:1"),
                          max_shards=2)
    _, summary, record = sess.run()
    events = record["events"]
    fails = [e for e in events["log"] if e.get("kind") == "fail"
             and not e.get("skipped")]
    assert len(fails) == 1
    assert fails[0]["redispatch_exact"] is True
    assert fails[0]["recovery_ms"] >= 0.0
    assert events["failures"] == 1
    assert events["availability"] == 1.0
    assert events["checksum"] == events["fault_free"]["checksum"]
    assert summary.completed == summary.offered


def test_scheduler_state_survives_restart(tmp_path):
    """Serve, checkpoint mid-session, restore into a fresh session, and
    finish: the resumed session completes exactly the remaining
    requests and the combined results are bit-identical to an
    uninterrupted run (same checksum over the same rid set)."""
    from repro.serving import ElasticSession, checkpoint_session, session
    assert hasattr(session, "checkpoint_session")
    cfg = _elastic_cfg()

    straight = ElasticSession(cfg)
    log1 = straight.serve(chaos=False)
    rids1 = {r.request.rid for r in log1.results if r.ok}

    interrupted = ElasticSession(cfg)
    interrupted.serve(chaos=False, stop_after_batches=2)
    step = checkpoint_session(interrupted, tmp_path)
    assert ckpt.latest_step(tmp_path) == step
    extra = ckpt.checkpoint_meta(tmp_path, step)["extra"]
    assert extra["kernel"] == cfg.kernel
    assert extra["n_completed"] == len(interrupted._state["fingerprints"])

    resumed = ElasticSession.restore(cfg, tmp_path)
    done_before = set(resumed._resume["completed"])
    log3 = resumed.serve(chaos=False)
    rids3 = {r.request.rid for r in log3.results if r.ok}
    # the resumed leg serves only what the checkpoint had not finished,
    # and together the two legs cover the uninterrupted run exactly
    assert rids3.isdisjoint(done_before)
    assert rids1 == rids3 | done_before
    assert straight.checksum() == resumed.checksum()


def test_session_restore_rejects_mismatched_seed(tmp_path):
    """A session checkpoint from different traffic must be refused, not
    silently adopted — mirrors the engine-cache leaf validation."""
    from repro.serving import ElasticSession, checkpoint_session
    sess = ElasticSession(_elastic_cfg())
    sess.serve(chaos=False, stop_after_batches=1)
    checkpoint_session(sess, tmp_path)
    with pytest.raises(ValueError, match="cache leaf mismatch"):
        ElasticSession.restore(_elastic_cfg(seed=1), tmp_path)


def test_async_checkpointer_surfaces_writer_errors(tmp_path):
    """A failed background save raises on the *caller's* thread at the
    next wait(), and the error is consumed (wait is then a no-op)."""
    blocker = tmp_path / "occupied"
    blocker.write_text("not a directory")
    w = ckpt.AsyncCheckpointer(blocker / "ckpts")
    w.save(1, {"x": jnp.zeros(2)})
    with pytest.raises(OSError):
        w.wait()
    w.wait()  # error consumed; idempotent


def test_corrupt_checkpoint_falls_back_with_warning(tmp_path):
    """Resume-from-newest skips an unreadable step with a structured
    warning record and restores the previous complete one; naming the
    corrupt step explicitly stays strict."""
    from repro.obs.log import LOG

    tree = {"x": jnp.arange(4.0)}
    ckpt.save(tmp_path, 1, tree)
    ckpt.save(tmp_path, 2, jax.tree.map(lambda x: x * 10, tree))
    (tmp_path / "step_00000002" / "arrays.npz").write_bytes(b"garbage")
    with LOG.capture() as records:
        restored = ckpt.restore(tmp_path, tree)
    warned = [r for r in records
              if r.level == "warning" and "unreadable" in r.msg]
    assert warned and warned[0].fields["step"] == "step_00000002"
    assert np.array_equal(np.asarray(restored["x"]), np.arange(4.0))
    with pytest.raises(Exception):
        ckpt.restore(tmp_path, tree, step=2)  # explicit step: strict


def test_pipeline_determinism_and_host_sharding():
    cfg = reduced(get_arch("deepseek-7b"))
    full = TokenPipeline(cfg, global_batch=8, seq=16, num_hosts=1)
    h0 = TokenPipeline(cfg, global_batch=8, seq=16, num_hosts=2,
                       host_index=0)
    again = TokenPipeline(cfg, global_batch=8, seq=16, num_hosts=2,
                          host_index=0)
    b1, b2 = h0.batch(7), again.batch(7)
    assert np.array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert h0.local_batch == 4 and full.local_batch == 8
    # different steps and hosts give different data
    h1 = TokenPipeline(cfg, global_batch=8, seq=16, num_hosts=2,
                       host_index=1)
    assert not np.array_equal(np.asarray(h0.batch(7)["tokens"]),
                              np.asarray(h1.batch(7)["tokens"]))
    assert not np.array_equal(np.asarray(h0.batch(7)["tokens"]),
                              np.asarray(h0.batch(8)["tokens"]))
