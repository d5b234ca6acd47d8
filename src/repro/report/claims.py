"""Per-record claim verification: measurements vs. the paper's theory.

Joins every :class:`~repro.report.records.BenchRecord` back to the
analytic layer (``repro.core.advisor`` / ``bounds`` / ``balance``) and
checks the paper's claims record by record:

* **ceiling** (Eq. 23/24) -- the recorded matrix-engine speedup ceiling
  never exceeds min(2 - 2/(1+alpha), 1 + I/B), and never drops below
  the fully-overlapped floor of 1.0 (Eq. 17).
* **routing** (§6) -- memory-bound records route ``engine='auto'`` to
  the vector engine; compute-bound records to the matrix engine.
* **accuracy** (§5 methodology) -- both engine variants reproduce the
  oracle within a per-dtype tolerance: same result through the same
  memory path.
* **boundedness** (Eq. 4) -- the recorded memory-bound flag matches a
  fresh I < B_vector derivation from the recorded intensity.

Schema-4 serving records (sessions under traffic) get their own claim
set (:data:`SERVING_CLAIMS`): the Eq. 23/24 **ceiling**, §6 routing,
and Eq. 4 boundedness are re-derived exactly as above, plus two
internal-consistency claims — latency percentiles must be non-negative
and monotone (p50 ≤ p95 ≤ p99), and goodput must be consistent with
the SLO-attainment and completion accounting (goodput =
attained/duration, never exceeding throughput) — so a hand-edited or
buggy serving record cannot publish an impossible latency/goodput
story or a ceiling the theory forbids.

Schema-5 sweep points carrying a ``shard_spec`` additionally pass the
**shard claims** (:data:`SHARD_CLAIMS`), which pin the paper's
per-device verdict onto every shard of a mesh execution:

* **shard_ceiling** — the spec is sane (known kind, 1 ≤ num_shards ≤
  mesh devices, halo ≥ 0), the worst shard's intensity never exceeds
  the unsharded intensity (splitting W and Q together cannot raise I;
  halo/replication traffic only lowers it), a memory-bound kernel
  stays memory-bound per shard (I_shard < B_vector: per-shard
  bandwidth, not the compute engine, sets the roof), and the recorded
  matrix-engine ceiling still obeys Eq. 23/24 evaluated at the
  *per-shard* intensity.
* **shard_traffic** — aggregate-bandwidth consistency: the bytes all
  shards move sum to at least the unsharded total (sharding never
  invents traffic savings), the worst shard times num_shards covers
  the aggregate (max × N ≥ Σ), no single shard moves more bytes than
  the unsharded kernel (replication/halo can at most re-read the
  whole input, capping the aggregate at N × total), and a halo-free
  data/head split moves *exactly* the unsharded bytes — any overhead
  must come from declared halo rows or rowblock operand replication.

Schema-6 sweep points carrying ``mesh_exec`` (measured real-mesh
execution) additionally pass the **mesh claims**
(:data:`MESH_CLAIMS`), which pin the measurements to physics and to
the plan's wire accounting:

* **collective_cost** — the measured timings are sane (mesh wall > 0,
  virtual analogue > 0, collective ≥ 0, devices matches the shard
  plan's width) and the collective time is *consistent with the
  plan*: a plan that wires zero bytes (``shard_spec.wire_bytes == 0``
  — data/head/halo-free splits exchange nothing) must measure zero
  collective time, a plan with halo rows on a multi-device mesh must
  measure a nonzero one, the collective can't dominate the whole step
  by more than the probe's own overhead allows (collective ≤ 8 ×
  wall), and the implied wire bandwidth (wire_bytes / collective
  time) stays below any real interconnect (≤ 1 TB/s) — a hand-edited
  "collectives are free" record fails here.
* **mesh_skew** — the real-vs-virtual story holds together: the
  recorded skew equals mesh_wall/virtual, sits inside a generous
  anti-flake band (1/200 ≤ skew ≤ 200 — host-CPU "devices" share one
  socket, so real walls legitimately exceed the modeled clock, but an
  out-of-band skew means one of the two timing paths is broken), and
  the real-mesh output matched the oracle within the dtype tolerance
  (``mesh_max_err``) — the measured execution that produced the wall
  time computed the right answer through real ppermute halo exchange.

Schema-7 records (and serving schema 5) carrying the observability
``trace`` block additionally pass **trace_reconciliation**
(:data:`TRACE_CLAIMS`): the :mod:`repro.obs` tracer's independent
account of the same measurement must agree with the record it rode in
on.  For a bench record the span count equals the timing iterations,
the span-median microseconds equal ``ref_us_per_call`` within rounding
(the span *is* the sample — ``time_fn`` emits the recorded
(start, duration) pairs, so only serialization rounding may differ),
the roofline gauge (achieved GB/s, %-of-Eq.-4-bound,
%-of-Eq.-3-ceiling) re-derives exactly from the record's own traffic,
time, and hardware model, and a measured-mesh point's ``mesh_step``
spans reconcile against ``mesh_exec.mesh_wall_us``.  For a serving
record the virtual-clock batch spans equal the logged launches and the
summed span compute equals the log's compute total; a chaos session's
redispatch spans equal the applied failure count and every applied
failure/resize left its instant on the timeline.  A trace that drifts
from the evidence it narrates turns the report red.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple, Union

from ..core.advisor import EngineAdvisor
from ..core.balance import machine_balance
from ..core.bounds import tensor_core_upper_bound, workload_upper_bound
from ..core.hw import PLATFORMS, TPU_V5E, HardwareSpec
from ..core.intensity import KernelTraits
from ..obs.counters import roofline_sample
from .records import BenchRecord, RecordSet, ServingRecord

__all__ = ["CLAIMS", "ClaimResult", "ELASTIC_CLAIMS", "MESH_CLAIMS",
           "MODEL_CLAIMS", "SERVING_CLAIMS", "SHARD_CLAIMS", "TOLERANCE",
           "TRACE_CLAIMS", "ceiling_bound",
           "check_record", "check_records", "check_serving_record",
           "hw_for", "violations"]

#: Claim identifiers, in report order.
CLAIMS = ("ceiling", "routing", "accuracy", "boundedness")

#: Serving-record claim identifiers, in report order.
SERVING_CLAIMS = ("ceiling", "routing", "boundedness", "percentiles",
                  "goodput")

#: Extra claims for sweep points that executed under a mesh (schema 5
#: records with a ``shard_spec``), in report order.
SHARD_CLAIMS = ("shard_ceiling", "shard_traffic")

#: Extra claims for sweep points that *measured* a real multi-device
#: mesh execution (schema 6 records with ``mesh_exec``), in report
#: order.
MESH_CLAIMS = ("collective_cost", "mesh_skew")

#: Extra claim for serving sessions that carry a model-scale verdict
#: (lm records with a ``verdict`` payload).
MODEL_CLAIMS = ("model_verdict",)

#: Extra claim for chaos serving sessions (ElasticSession records with
#: an ``events`` payload): failures and resizes moved latency, never
#: results, and never past the availability/p99 floors.
ELASTIC_CLAIMS = ("elastic_integrity",)

#: Extra claim for records carrying the observability ``trace`` block
#: (bench schema 7 / serving schema 5): the tracer's independent
#: account of the measurement reconciles with the record it rode in on.
TRACE_CLAIMS = ("trace_reconciliation",)

#: Rounding slack for span-vs-record microsecond comparisons:
#: ``ref_us_per_call``/``mesh_wall_us`` are rounded to 0.1 µs at record
#: time and the span medians to 0.001 µs, so two exact-equal timings
#: may differ by half of the coarser step (0.05) plus the finer one.
_TRACE_US_SLACK = 0.051

#: Ceiling on the wire bandwidth a measured collective may imply
#: (wire_bytes / collective seconds).  1 TB/s comfortably exceeds any
#: host interconnect and sits above v5e ICI per-link rates, so only a
#: fabricated "collectives are free" record trips it.
_MAX_WIRE_BW = 1e12

#: Anti-flake band for the real-vs-virtual wall-clock skew.  Forced
#: host "devices" share one CPU socket, so a real mesh step
#: legitimately costs tens of times the modeled max-shard clock
#: (measured 5-45x on a 4-way host mesh); a skew outside
#: [1/200, 200] means one of the two timing paths broke.
_SKEW_BAND = 200.0

#: Max abs error allowed between an engine variant and its oracle.
#: bfloat16 has an 8-bit mantissa, so elementwise results on O(10)
#: magnitudes legitimately differ by ~2^-4.
TOLERANCE: Dict[str, float] = {"float32": 1e-4, "bfloat16": 0.125}

_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class ClaimResult:
    """Outcome of one claim check against one bench/serving record."""

    claim: str           # one of CLAIMS / SERVING_CLAIMS
    record: Union[BenchRecord, ServingRecord]
    passed: bool
    detail: str          # human-readable evidence string


def hw_for(recset: RecordSet,
           default: HardwareSpec = TPU_V5E) -> HardwareSpec:
    """Resolve a record set's ``env.hw_model`` to a HardwareSpec.

    Falls back to the TPU v5e model (paper Table 1 extended) when the
    record set predates schema 2 or names an unknown platform.
    """
    name = str(recset.env.get("hw_model", ""))
    for hw in PLATFORMS.values():
        if hw.name == name:
            return hw
    return default


def ceiling_bound(intensity: float, hw: HardwareSpec) -> float:
    """The paper's composite matrix-engine ceiling for one kernel.

    min(Eq. 23: 2 - 2/(1+alpha), Eq. 24: 1 + I/B_vector) -- the
    tightest bound any memory-bound record may report.
    """
    b_vec = machine_balance(hw, "vector")
    return min(tensor_core_upper_bound(hw.alpha),
               workload_upper_bound(intensity, b_vec))


def _analytic_checks(rec, hw: HardwareSpec,
                     routing_context: str = "") -> List[ClaimResult]:
    """The ceiling/routing/boundedness checks both record kinds share.

    Bench sweep points and serving sessions carry the same analytic
    join fields (intensity, memory_bound, engine_auto, mxu_ceiling),
    so Eq. 17/23/24, §6 routing, and Eq. 4 are verified by one
    implementation — the two record kinds can never drift onto
    different rules.
    """
    advice = EngineAdvisor(hw).advise(
        KernelTraits(rec.kernel, rec.intensity, 1.0))
    results = []

    bound = ceiling_bound(rec.intensity, hw)
    if rec.memory_bound:
        ceiling_ok = 1.0 - _EPS <= rec.mxu_ceiling <= bound + _EPS
        ceiling_detail = (f"recorded ceiling {rec.mxu_ceiling:.4g}x vs "
                          f"Eq. 23/24 bound {bound:.4g}x")
    else:
        # Compute-bound records escape Eq. 23/24; the ceiling may reach
        # the full engine ratio alpha but no further.
        ceiling_ok = 1.0 - _EPS <= rec.mxu_ceiling <= hw.alpha + _EPS
        ceiling_detail = (f"compute-bound: ceiling {rec.mxu_ceiling:.4g}x "
                          f"vs alpha {hw.alpha:.4g}")
    results.append(ClaimResult("ceiling", rec, ceiling_ok, ceiling_detail))

    routing_ok = rec.engine_auto == advice.engine and (
        not rec.memory_bound or rec.engine_auto == "vector")
    results.append(ClaimResult(
        "routing", rec, routing_ok,
        f"auto={rec.engine_auto} vs advisor={advice.engine} "
        f"(memory_bound={rec.memory_bound}{routing_context})"))

    results.append(ClaimResult(
        "boundedness", rec, rec.memory_bound == advice.memory_bound,
        f"recorded memory_bound={rec.memory_bound} vs derived "
        f"I={rec.intensity:.4g} < B_vec={machine_balance(hw, 'vector'):.4g} "
        f"-> {advice.memory_bound}"))
    return results


def _shard_checks(rec: BenchRecord,
                  hw: HardwareSpec) -> List[ClaimResult]:
    """The SHARD_CLAIMS for one mesh sweep point (see module docs).

    Re-derives the Eq. 23/24 ceiling at the *per-shard* intensity and
    bounds the aggregate traffic against the unsharded Q, so a record
    cannot claim a mesh execution that either beats the per-device
    ceiling on any shard or quietly moves fewer bytes than the
    unsharded kernel — the two ways a sharded "speedup" could lie.
    """
    spec = dict(rec.shard_spec or {})
    n = int(spec.get("num_shards", 0))
    halo = int(spec.get("halo", -1))
    kind = str(spec.get("kind", ""))
    total = float(spec.get("total_bytes", 0.0))
    agg = float(spec.get("agg_bytes", 0.0))
    worst = float(spec.get("shard_bytes", 0.0))
    i_shard = float(spec.get("shard_intensity", float("inf")))
    b_vec = machine_balance(hw, "vector")
    # rounding slack: byte totals are exact floats from the traits
    # model, but allow 1e-6 relative for serialization round-trips
    slack = 1e-6 * max(total, 1.0)

    sane = (kind in ("data", "rowblock", "head")
            and 1 <= n <= max(rec.mesh_devices, 1)
            and halo >= 0)
    i_ok = i_shard <= rec.intensity + _EPS
    if rec.memory_bound:
        bound = ceiling_bound(i_shard, hw)
        ceil_ok = i_shard < b_vec and rec.mxu_ceiling <= bound + _EPS
        detail = (f"kind={kind} shards={n}/{rec.mesh_devices} "
                  f"I_shard={i_shard:.4g} < B_vec={b_vec:.4g}; "
                  f"ceiling {rec.mxu_ceiling:.4g}x vs per-shard "
                  f"Eq. 23/24 bound {bound:.4g}x")
    else:
        ceil_ok = rec.mxu_ceiling <= hw.alpha + _EPS
        detail = (f"kind={kind} shards={n}/{rec.mesh_devices} "
                  f"compute-bound: ceiling {rec.mxu_ceiling:.4g}x vs "
                  f"alpha {hw.alpha:.4g}")
    shard_ceiling = ClaimResult("shard_ceiling", rec,
                                sane and i_ok and ceil_ok, detail)

    traffic_ok = (agg >= total - slack
                  and worst * n >= agg - slack
                  # no shard moves more bytes than the unsharded
                  # kernel (replication/halo can at most re-read the
                  # whole input), which caps the aggregate at N x
                  # total — a hand-edited 100x-traffic story fails here
                  and worst <= total + slack
                  and (halo > 0 or kind == "rowblock"
                       or abs(agg - total) <= slack))
    shard_traffic = ClaimResult(
        "shard_traffic", rec, traffic_ok,
        f"agg {agg:.4g} B vs total {total:.4g} B "
        f"(overhead {agg / total - 1.0 if total else 0.0:+.2%}), "
        f"worst shard {worst:.4g} B x {n}")
    return [shard_ceiling, shard_traffic]


def _mesh_checks(rec: BenchRecord,
                 hw: HardwareSpec) -> List[ClaimResult]:
    """The MESH_CLAIMS for one measured real-mesh point (module docs).

    Ties the three measured timings to each other and to the shard
    plan's wire accounting: a record cannot claim a free collective
    over declared halo bytes, an impossible wire bandwidth, or a
    real-vs-virtual skew the shared-socket host platform cannot
    produce — and the wall time only counts if the real execution
    that produced it reproduced the oracle.
    """
    mex = dict(rec.mesh_exec or {})
    spec = dict(rec.shard_spec or {})
    devices = int(mex.get("devices", 0))
    wall = float(mex.get("mesh_wall_us", 0.0))
    coll = float(mex.get("collective_us", -1.0))
    virt = float(mex.get("virtual_us", 0.0))
    skew = float(mex.get("skew", 0.0))
    wire = float(spec.get("wire_bytes", 0.0))
    n = int(spec.get("num_shards", 0))

    sane = (wall > 0.0 and virt > 0.0 and coll >= 0.0
            and 1 <= devices and devices == n)
    if wire <= 0.0:
        wire_ok = coll == 0.0
        wire_detail = "plan wires 0 B -> collective must measure 0"
    else:
        # halo bytes really crossed the mesh: nonzero measured time,
        # not dominating the step beyond probe overhead, and implying
        # a physically possible wire bandwidth
        bw = wire / (coll * 1e-6) if coll > 0 else float("inf")
        wire_ok = (devices < 2) or (0.0 < coll <= 8.0 * wall
                                    and bw <= _MAX_WIRE_BW)
        wire_detail = (f"wire {wire:.4g} B in {coll:.4g} us -> "
                       f"{bw / 1e9:.4g} GB/s")
    collective_cost = ClaimResult(
        "collective_cost", rec, sane and wire_ok,
        f"devices={devices}/{n} wall={wall:.4g} us "
        f"coll={coll:.4g} us virt={virt:.4g} us; {wire_detail}")

    tol = TOLERANCE.get(rec.dtype, TOLERANCE["float32"])
    mesh_err = float(mex.get("mesh_max_err", float("inf")))
    skew_expect = wall / virt if virt > 0 else 0.0
    skew_ok = (virt > 0
               and abs(skew - skew_expect) <= 0.01 * max(skew_expect, 1.0)
               and 1.0 / _SKEW_BAND <= skew <= _SKEW_BAND
               and mesh_err <= tol)
    mesh_skew = ClaimResult(
        "mesh_skew", rec, skew_ok,
        f"skew {skew:.4g} (= wall {wall:.4g} / virtual {virt:.4g}) in "
        f"[1/{_SKEW_BAND:g}, {_SKEW_BAND:g}]; mesh_max_err "
        f"{mesh_err:.3g} vs {rec.dtype} tolerance {tol:g}")
    return [collective_cost, mesh_skew]


def _trace_checks(rec: BenchRecord,
                  hw: HardwareSpec) -> List[ClaimResult]:
    """The TRACE_CLAIMS check for one bench record's trace block.

    ``time_fn`` emits one span per timing iteration carrying the
    *recorded* (start, duration) sample — the span is the sample, not a
    re-measurement — so the reconciliation tolerance is pure
    serialization rounding (:data:`_TRACE_US_SLACK`).  The roofline
    gauge must re-derive from the record's own traffic bytes, recorded
    median, and hardware model via the same Eq. 2/3/4 arithmetic the
    live counters use (``repro.obs.counters.roofline_sample``) — a
    trace cannot publish an achieved bandwidth its own record's numbers
    don't produce.
    """
    tr = dict(rec.trace or {})
    problems: List[str] = []

    if tr.get("clock") != "wall":
        problems.append(f"bench trace on clock {tr.get('clock')!r}")
    spans = int(tr.get("spans", -1))
    if rec.iters is not None and spans != rec.iters:
        problems.append(f"{spans} ref spans != {rec.iters} timing iters")
    med = float(tr.get("span_median_us", -1.0))
    if abs(med - rec.ref_us_per_call) > _TRACE_US_SLACK:
        problems.append(f"span median {med:.4g} us != ref_us_per_call "
                        f"{rec.ref_us_per_call:.4g} us")

    roof = dict(tr.get("roofline") or {})
    if not roof:
        problems.append("missing roofline gauge")
    else:
        traffic = float(roof.get("traffic_bytes", 0.0))
        work = float(roof.get("work_flops", 0.0))
        meas = float(roof.get("measured_us", -1.0))
        if traffic <= 0.0:
            problems.append(f"roofline traffic {traffic:.4g} B <= 0")
        else:
            if abs(work / traffic - rec.intensity) > \
                    1e-6 * max(rec.intensity, 1.0):
                problems.append(
                    f"roofline W/Q {work / traffic:.4g} != recorded "
                    f"intensity {rec.intensity:.4g}")
            if abs(meas - rec.ref_us_per_call) > 1e-3:
                problems.append(f"roofline measured {meas:.4g} us != "
                                f"ref_us_per_call "
                                f"{rec.ref_us_per_call:.4g} us")
            expect = roofline_sample(
                KernelTraits(rec.kernel, work, traffic), hw, rec.engine,
                rec.dtype, rec.ref_us_per_call)
            for field in ("achieved_gbs", "pct_of_bound",
                          "pct_of_ceiling"):
                got = float(roof.get(field, -1.0))
                want = float(getattr(expect, field))
                if abs(got - want) > 1e-4 + 1e-6 * abs(want):
                    problems.append(f"roofline {field} {got:.6g} != "
                                    f"re-derived {want:.6g}")

    mesh = dict(tr.get("mesh") or {})
    if rec.mesh_exec:
        wall = float(dict(rec.mesh_exec).get("mesh_wall_us", 0.0))
        if not mesh:
            problems.append("measured-mesh record without mesh trace")
        else:
            if int(mesh.get("spans", 0)) < 1:
                problems.append("no mesh_step spans")
            if abs(float(mesh.get("mesh_wall_us", -1.0)) - wall) > 1e-6:
                problems.append(
                    f"mesh trace wall {mesh.get('mesh_wall_us')!r} != "
                    f"mesh_exec {wall:.4g} us")
            m_med = float(mesh.get("span_median_us", -1.0))
            if abs(m_med - wall) > _TRACE_US_SLACK:
                problems.append(f"mesh span median {m_med:.4g} us != "
                                f"mesh_wall_us {wall:.4g} us")
    elif mesh:
        problems.append("mesh trace block on a non-mesh record")

    detail = (f"{spans} spans, median {med:.4g} us vs ref "
              f"{rec.ref_us_per_call:.4g} us, roofline re-derived"
              + (f"; problems: {'; '.join(problems[:4])}" if problems
                 else ""))
    return [ClaimResult("trace_reconciliation", rec, not problems, detail)]


def _serving_trace_checks(rec: ServingRecord) -> List[ClaimResult]:
    """The TRACE_CLAIMS check for one serving record's trace block.

    Two independently-kept accounts of the same virtual timeline — the
    tracer's spans (emitted inside the serving loop) and the
    :class:`~repro.serving.scheduler.ServingLog`'s batch tuples — must
    tell the same story: span count == logged launches, one queue span
    per completed request, summed span compute == summed logged compute
    (float-rounding tolerance).  A chaos session's redispatch spans
    must equal the applied failure count, and every applied
    failure/resize must have left its instant on the timeline (skipped
    injections leave none, so the instant count is bounded by the
    event log's skipped entries).
    """
    tr = dict(rec.trace or {})
    problems: List[str] = []

    if tr.get("clock") != "virtual":
        problems.append(f"serving trace on clock {tr.get('clock')!r}")
    batch_spans = int(tr.get("batch_spans", -1))
    if batch_spans != rec.batches:
        problems.append(f"{batch_spans} batch spans != {rec.batches} "
                        f"logged batches")
    queue_spans = int(tr.get("queue_spans", -1))
    if queue_spans != rec.completed:
        problems.append(f"{queue_spans} queue spans != {rec.completed} "
                        f"completed requests")
    span_ms = float(tr.get("span_compute_ms", -1.0))
    log_ms = float(tr.get("log_compute_ms", -2.0))
    if abs(span_ms - log_ms) > 0.01:
        problems.append(f"span compute {span_ms:.4g} ms != logged "
                        f"compute {log_ms:.4g} ms")

    if rec.events:
        ev = dict(rec.events)
        fails = int(ev.get("failures", -1))
        resizes = int(ev.get("resizes", -1))
        skipped_fails = sum(1 for e in ev.get("log", [])
                            if str(e.get("kind")) == "fail"
                            and e.get("skipped"))
        redis = int(tr.get("redispatch_spans", -1))
        if redis != fails:
            problems.append(f"{redis} redispatch spans != {fails} "
                            f"applied failures")
        instants = int(tr.get("chaos_instants", -1))
        # every applied failure was armed by an instant-emitting
        # injection and every applied resize emitted its instant;
        # armed-but-skipped failures emit an instant without a log
        # "applied" entry, so the count may exceed the floor by at
        # most the skipped-failure tally
        lo, hi = fails + resizes, fails + skipped_fails + resizes
        if not lo <= instants <= hi:
            problems.append(f"{instants} chaos instants outside "
                            f"[{lo}, {hi}] (failures={fails}, "
                            f"resizes={resizes}, skipped={skipped_fails})")

    detail = (f"{batch_spans} batch + {queue_spans} queue spans, span "
              f"compute {span_ms:.4g} ms vs log {log_ms:.4g} ms"
              + (f"; problems: {'; '.join(problems[:4])}" if problems
                 else ""))
    return [ClaimResult("trace_reconciliation", rec, not problems, detail)]


def _verdict_checks(rec: ServingRecord,
                    hw: HardwareSpec) -> List[ClaimResult]:
    """The MODEL_CLAIMS check for one lm session's verdict payload.

    The verdict is the per-op Eq. 2 classification of one decode step
    at model scale (``repro.models.advisor_map``).  The claim
    re-derives every row and the whole-step accounting:

    * per-op intensity equals flops/bytes, the memory_bound flag
      matches a fresh Eq. 4 test, a memory-bound op routes to the
      vector engine (§6), and its recorded ceiling obeys Eq. 23/24 at
      that op's intensity;
    * the time and byte fractions each sum to 1 (every op of the step
      is accounted for — nothing hidden, nothing double-counted);
    * the per-op times sum to the measured mean decode-step wall time
      within rounding tolerance (the classification covers the whole
      measured step, not a convenient subset);
    * the headline memory-bound fractions equal the sum over
      memory-bound ops.
    """
    v = dict(rec.verdict or {})
    ops = list(v.get("ops", []))
    step_ms = float(v.get("step_time_ms", 0.0))
    b_vec = machine_balance(hw, "vector")
    problems: List[str] = []
    if not ops:
        problems.append("empty ops list")

    tsum = bsum = mb_t = mb_b = t_ms = 0.0
    for op in ops:
        name = str(op.get("name", "?"))
        W, Q = float(op.get("flops", 0.0)), float(op.get("bytes", 0.0))
        intensity = float(op.get("intensity", -1.0))
        mb = bool(op.get("memory_bound"))
        engine = str(op.get("engine", ""))
        ceil = float(op.get("mxu_ceiling", 0.0))
        tf, bf = float(op.get("time_frac", 0.0)), \
            float(op.get("bytes_frac", 0.0))
        if Q <= 0.0:
            problems.append(f"{name}: bytes {Q:.4g} <= 0")
            continue
        derived_i = W / Q
        if abs(intensity - derived_i) > 1e-6 * max(derived_i, 1.0):
            problems.append(f"{name}: intensity {intensity:.4g} != "
                            f"W/Q {derived_i:.4g}")
        if mb != (derived_i < b_vec):
            problems.append(f"{name}: memory_bound={mb} vs Eq. 4 "
                            f"I={derived_i:.4g} < B_vec={b_vec:.4g}")
        if mb and engine != "vector":
            problems.append(f"{name}: memory-bound routed to {engine}")
        bound = (ceiling_bound(derived_i, hw) if mb else hw.alpha)
        if not (1.0 - _EPS <= ceil <= bound + _EPS):
            problems.append(f"{name}: ceiling {ceil:.4g}x outside "
                            f"[1, {bound:.4g}]")
        if not (0.0 <= tf <= 1.0 + _EPS and 0.0 <= bf <= 1.0 + _EPS):
            problems.append(f"{name}: fraction outside [0, 1]")
        tsum += tf
        bsum += bf
        t_ms += float(op.get("time_ms", 0.0))
        if mb:
            mb_t += tf
            mb_b += bf

    if ops:
        if abs(tsum - 1.0) > 1e-4:
            problems.append(f"time fractions sum to {tsum:.6g} != 1")
        if abs(bsum - 1.0) > 1e-4:
            problems.append(f"byte fractions sum to {bsum:.6g} != 1")
        # per-op time_ms rows are rounded independently at record time
        if abs(t_ms - step_ms) > 1e-3 * max(step_ms, 1.0) + 1e-3 * len(ops):
            problems.append(f"per-op times sum to {t_ms:.4g} ms vs "
                            f"measured step {step_ms:.4g} ms")
        head_t = float(v.get("memory_bound_time_frac", -1.0))
        head_b = float(v.get("memory_bound_bytes_frac", -1.0))
        if abs(head_t - mb_t) > 1e-4 or abs(head_b - mb_b) > 1e-4:
            problems.append(f"headline fractions ({head_t:.4g}, "
                            f"{head_b:.4g}) != per-op sums "
                            f"({mb_t:.4g}, {mb_b:.4g})")

    detail = (f"{len(ops)} ops, memory-bound time frac {mb_t:.4g}, "
              f"step {step_ms:.4g} ms"
              + (f"; problems: {'; '.join(problems[:4])}" if problems
                 else ""))
    return [ClaimResult("model_verdict", rec, not problems, detail)]


def _elastic_checks(rec: ServingRecord,
                    hw: HardwareSpec) -> List[ClaimResult]:
    """The ELASTIC_CLAIMS check for one chaos session's events payload.

    The integrity contract of ``repro.serving.elastic``: an injected
    shard failure or mesh resize may cost latency, never answers.
    Verified from the record alone:

    * the chaos session's fingerprint checksum equals the fault-free
      replay's **exactly** (bit-exact re-dispatch and re-shard — the
      same float64 or the claim is red);
    * completions match the fault-free replay and the recorded
      availability is both consistent with completed/offered and at or
      above the recorded target;
    * the chaos p99 stays within ``p99_bound x fault-free p99 +
      p99_slack_ms`` (failure recovery is charged to the clock, so
      degradation is expected — unbounded degradation is not);
    * every log entry is sane: known kind, non-negative time, every
      *applied* failure re-dispatched bit-exactly with non-negative
      recovery latency, every resize between valid widths with
      ``dp_rescale`` = to/from and a bit-exact re-shard
      (``reshard_exact``), and the failure/resize counters match the
      log.

    The ceiling/routing/boundedness claims run on the same record
    independently, so "the Eq. 23/24 story holds across events" is
    checked by construction: the record's analytic fields come from
    the same memoized Advice at every width.
    """
    del hw  # the analytic claims run separately on the same record
    ev = dict(rec.events or {})
    ff = dict(ev.get("fault_free", {}))
    problems: List[str] = []

    checksum = ev.get("checksum")
    ff_checksum = ff.get("checksum")
    if checksum is None or ff_checksum is None:
        problems.append("missing checksum")
    elif float(checksum) != float(ff_checksum):
        problems.append(f"checksum {checksum!r} != fault-free "
                        f"{ff_checksum!r}")

    if int(ff.get("completed", -1)) != rec.completed or \
            int(ff.get("offered", -1)) != rec.offered:
        problems.append(
            f"completions {rec.completed}/{rec.offered} != fault-free "
            f"{ff.get('completed')}/{ff.get('offered')}")

    avail = float(ev.get("availability", -1.0))
    target = float(ev.get("availability_target", -1.0))
    derived = (rec.completed / rec.offered if rec.offered > 0 else 1.0)
    if not 0.0 < target <= 1.0:
        problems.append(f"bad availability target {target!r}")
    if abs(avail - derived) > 1e-6 + _EPS:
        problems.append(f"availability {avail:.6g} != "
                        f"completed/offered {derived:.6g}")
    if avail < target - _EPS:
        problems.append(f"availability {avail:.6g} < target {target:.6g}")

    bound = float(ev.get("p99_bound", 0.0))
    slack = float(ev.get("p99_slack_ms", 0.0))
    ff_p99 = float(ff.get("p99_ms", 0.0))
    limit = bound * ff_p99 + slack
    if bound <= 0.0:
        problems.append(f"bad p99 bound {bound!r}")
    elif rec.p99_ms > limit + _EPS:
        problems.append(f"p99 {rec.p99_ms:.4g} ms > bound "
                        f"{bound:g} x {ff_p99:.4g} + {slack:g} ms")

    applied_fails = applied_resizes = 0
    for i, entry in enumerate(ev.get("log", [])):
        kind = str(entry.get("kind", "?"))
        at_s = float(entry.get("at_s", -1.0))
        if kind not in ("fail", "resize") or at_s < 0.0:
            problems.append(f"log[{i}]: bad entry kind={kind} at={at_s}")
            continue
        if entry.get("skipped"):
            continue
        if kind == "fail":
            applied_fails += 1
            if not entry.get("redispatch_exact"):
                problems.append(f"log[{i}]: failure re-dispatch not "
                                f"bit-exact")
            if float(entry.get("recovery_ms", -1.0)) < 0.0:
                problems.append(f"log[{i}]: negative recovery latency")
        else:
            applied_resizes += 1
            frm, to = int(entry.get("from", 0)), int(entry.get("to", 0))
            rescale = float(entry.get("dp_rescale", 0.0))
            if frm < 1 or to < 1:
                problems.append(f"log[{i}]: resize widths {frm}->{to}")
            elif abs(rescale - to / frm) > _EPS:
                problems.append(f"log[{i}]: dp_rescale {rescale:.4g} "
                                f"!= {to}/{frm}")
            if not entry.get("reshard_exact"):
                problems.append(f"log[{i}]: re-shard not bit-exact")
    if applied_fails != int(ev.get("failures", -1)) or \
            applied_resizes != int(ev.get("resizes", -1)):
        problems.append(
            f"counters ({ev.get('failures')}, {ev.get('resizes')}) != "
            f"log ({applied_fails}, {applied_resizes})")

    detail = (f"{applied_fails} failures + {applied_resizes} resizes, "
              f"availability {avail:.4g} >= {target:.4g}, checksum "
              f"bit-exact vs fault-free replay"
              + (f"; problems: {'; '.join(problems[:4])}" if problems
                 else ""))
    return [ClaimResult("elastic_integrity", rec, not problems, detail)]


def check_record(rec: BenchRecord,
                 hw: HardwareSpec = TPU_V5E) -> Tuple[ClaimResult, ...]:
    """Verify all four paper claims (Eq. 4, Eq. 17/23/24, §6) for one record.

    Returns one :class:`ClaimResult` per entry in :data:`CLAIMS`, in
    order, re-deriving the advisor's decision from the recorded
    intensity so a stale or hand-edited record cannot pass silently.
    Mesh sweep points (schema 5 with a ``shard_spec``) additionally get
    one result per entry in :data:`SHARD_CLAIMS` — the per-device
    verdict re-checked per shard — and measured real-mesh points
    (schema 6 with ``mesh_exec``) one per entry in
    :data:`MESH_CLAIMS`.  Records carrying the observability ``trace``
    block (schema 7) additionally pass :data:`TRACE_CLAIMS`.
    """
    ceiling, routing, boundedness = _analytic_checks(rec, hw)

    tol = TOLERANCE.get(rec.dtype, TOLERANCE["float32"])
    accuracy = ClaimResult(
        "accuracy", rec, rec.max_err <= tol,
        f"max_err {rec.max_err:.3g} vs {rec.dtype} tolerance {tol:g}")
    out = [ceiling, routing, accuracy, boundedness]
    if rec.shard_spec:
        out.extend(_shard_checks(rec, hw))
    if rec.mesh_exec:
        out.extend(_mesh_checks(rec, hw))
    if rec.trace:
        out.extend(_trace_checks(rec, hw))
    return tuple(out)


def check_serving_record(rec: ServingRecord,
                         hw: HardwareSpec = TPU_V5E,
                         ) -> Tuple[ClaimResult, ...]:
    """Verify the serving claims (§6 routing under load, Eq. 4, latency
    and goodput consistency) for one schema-4 session record.

    Returns one :class:`ClaimResult` per entry in
    :data:`SERVING_CLAIMS`, in order, re-deriving the advisor's
    decision from the recorded intensity so the paper's routing story
    is checked in steady state, not just per call.  Records carrying a
    model-scale ``verdict`` payload (lm sessions) additionally get one
    result per entry in :data:`MODEL_CLAIMS` — the per-op
    classification re-derived and reconciled against the measured
    decode-step wall time — and records carrying a chaos ``events``
    payload (ElasticSession) one per entry in :data:`ELASTIC_CLAIMS`,
    the failures-move-latency-never-results contract.  Records carrying
    the observability ``trace`` block (serving schema 5) additionally
    pass :data:`TRACE_CLAIMS`.
    """
    # Eq. 17/23/24, §6 routing, Eq. 4: the same checks as per-call
    # sweep points, via the shared helper (a record claiming a bigger
    # matrix-engine win than the theory allows is a violation whether
    # it was measured per call or under traffic)
    ceiling, routing, boundedness = _analytic_checks(
        rec, hw, routing_context=f", workload={rec.workload}")
    results = [ceiling, routing, boundedness]

    pct_ok = (0.0 <= rec.p50_ms <= rec.p95_ms + _EPS
              and rec.p95_ms <= rec.p99_ms + _EPS
              and rec.queue_p50_ms >= 0.0 and rec.compute_p50_ms >= 0.0)
    results.append(ClaimResult(
        "percentiles", rec, pct_ok,
        f"p50={rec.p50_ms:.4g} <= p95={rec.p95_ms:.4g} <= "
        f"p99={rec.p99_ms:.4g} ms, queue/compute splits >= 0"))

    throughput = (rec.completed / rec.duration_s
                  if rec.duration_s > 0 else 0.0)
    # goodput = attained/duration; attainment and goodput are rounded
    # independently at record time, so allow that rounding slack
    expect = rec.slo_attainment * throughput
    slack = 0.5 + 0.01 * max(throughput, 1.0)
    goodput_ok = (0.0 <= rec.slo_attainment <= 1.0 + _EPS
                  and rec.completed <= rec.offered
                  and rec.goodput_rps <= throughput + slack
                  and abs(rec.goodput_rps - expect) <= slack)
    results.append(ClaimResult(
        "goodput", rec, goodput_ok,
        f"goodput {rec.goodput_rps:.4g}/s vs attainment "
        f"{rec.slo_attainment:.4g} x throughput {throughput:.4g}/s "
        f"({rec.completed}/{rec.offered} completed)"))
    if rec.verdict:
        results.extend(_verdict_checks(rec, hw))
    if rec.events:
        results.extend(_elastic_checks(rec, hw))
    if rec.trace:
        results.extend(_serving_trace_checks(rec))
    return tuple(results)


def check_records(recsets: Sequence[RecordSet]) -> List[ClaimResult]:
    """Run the kind-appropriate checks over every record of every set.

    Bench sets go through :func:`check_record`, serving sets through
    :func:`check_serving_record`.  The hardware model is resolved per
    record set from its environment metadata, so mixed-platform runs/
    directories verify correctly.
    """
    out: List[ClaimResult] = []
    for rs in recsets:
        hw = hw_for(rs)
        check = (check_serving_record if rs.kind == "serving"
                 else check_record)
        for rec in rs.records:
            out.extend(check(rec, hw))
    return out


def violations(results: Iterable[ClaimResult]) -> List[ClaimResult]:
    """The failing subset of *results* -- empty iff the paper's story holds."""
    return [r for r in results if not r.passed]
