"""Structural validation of the GitHub Actions workflows (the
actionlint-equivalent gate runnable in this container): both workflows
must parse as YAML and carry the shapes CI correctness depends on —
the split lint+unit/slow matrix with cancel-in-progress on PRs, and
the scheduled nightly sweep job with artifact upload."""
import pathlib

import pytest
import yaml

WORKFLOWS = pathlib.Path(__file__).resolve().parent.parent / \
    ".github" / "workflows"


def _load(name):
    wf = yaml.safe_load((WORKFLOWS / name).read_text())
    assert isinstance(wf, dict), name
    return wf


def _on(wf):
    # YAML 1.1 parses the bare key `on` as boolean True
    return wf.get("on", wf.get(True))


def _run_text(job):
    return "\n".join(s.get("run", "") for s in job["steps"])


@pytest.mark.parametrize("name", ["ci.yml", "nightly.yml"])
def test_workflow_is_structurally_valid(name):
    """Every job has runs-on + timeout, every step has uses xor run."""
    wf = _load(name)
    assert _on(wf), f"{name}: no triggers"
    assert wf.get("jobs"), f"{name}: no jobs"
    for jname, job in wf["jobs"].items():
        assert "runs-on" in job, f"{name}:{jname} missing runs-on"
        assert "timeout-minutes" in job, f"{name}:{jname} missing timeout"
        assert job.get("steps"), f"{name}:{jname} has no steps"
        for i, step in enumerate(job["steps"]):
            has_uses, has_run = "uses" in step, "run" in step
            assert has_uses != has_run, \
                f"{name}:{jname} step {i} needs exactly one of uses/run"


def test_ci_matrix_split():
    wf = _load("ci.yml")
    jobs = wf["jobs"]
    assert set(jobs) == {"lint-unit", "mesh-smoke", "lm-smoke",
                         "chaos-smoke", "trace-smoke", "slow"}

    lint = jobs["lint-unit"]
    matrix = lint["strategy"]["matrix"]["python-version"]
    assert matrix == ["3.10", "3.11", "3.12"]
    runs = _run_text(lint)
    # the fast job deselects the distributed tier by marker (the tiers
    # are declared in pyproject [tool.pytest.ini_options].markers) and
    # lints the tree
    assert 'pytest -q -m "not distributed"' in runs
    assert "ruff check" in runs
    assert "ruff format --check" in runs
    # ... and still regenerate + drift-check the claims report
    assert "benchmarks.run report" in runs
    assert "git diff --exit-code REPORT.md" in runs

    slow = jobs["slow"]
    assert "-m distributed" in _run_text(slow)
    assert "tests/test_distributed.py" in _run_text(slow)
    # the fast job must NOT run the full tier-1 suite (that is the
    # point of the split)
    assert "pytest -q\n" not in runs + "\n"


def test_ci_cancels_superseded_pr_runs():
    wf = _load("ci.yml")
    conc = wf["concurrency"]
    assert "github.ref" in conc["group"]
    assert "cancel-in-progress" in conc
    assert "pull_request" in str(conc["cancel-in-progress"])


def test_ci_pr_gate_sweeps_static_tiles():
    runs = _run_text(_load("ci.yml")["jobs"]["lint-unit"])
    # the tile is each kernel's own decision: no sweep takes a tile cache
    assert "benchmarks.run scale axpy --out runs-ci" in runs
    assert "--tuned" not in runs
    assert "benchmarks.compare runs runs-ci" in runs
    # the bench gate must not demand serving coverage of a bench-only
    # candidate sweep (and vice versa)
    assert "--kind bench" in runs


def test_ci_serve_smoke_gate():
    """The fast serve-smoke: a short Poisson run on the two cheapest
    families, gated on p99/goodput against the committed baseline —
    scoped to --mesh 1 so it is never blamed for the sharded chaos
    baseline (chaos-smoke gates that width)."""
    runs = _run_text(_load("ci.yml")["jobs"]["lint-unit"])
    assert "benchmarks.run serve --workload poisson" in runs
    assert "--kernels scale,axpy" in runs
    assert "benchmarks.compare runs runs-ci-serve" in runs
    assert "--kind serving --mesh 1" in runs


def test_ci_docs_link_check_step():
    """docs/ integrity is a named PR-CI step (dead links go red)."""
    runs = _run_text(_load("ci.yml")["jobs"]["lint-unit"])
    assert "pytest -q tests/test_docs.py" in runs


def test_ci_mesh_smoke_job():
    """The 2-way-mesh smoke: scale (data) + stencil (rowblock + halo)
    swept under --mesh 2 and gated with the bench compare gate against
    the committed mesh baseline, without touching other mesh widths."""
    job = _load("ci.yml")["jobs"]["mesh-smoke"]
    runs = _run_text(job)
    assert "benchmarks.run scale stencil --mesh 2" in runs
    assert "--tuned" not in runs
    assert "benchmarks.compare runs runs-ci-mesh" in runs
    assert "--kind bench" in runs and "--mesh 2" in runs
    uploads = [s for s in job["steps"]
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and "runs-ci-mesh" in uploads[0]["with"]["path"]
    # the fast gate stays scoped to the single-device sweep so the two
    # jobs never double-gate (or double-miss) a mesh width
    lint_runs = _run_text(_load("ci.yml")["jobs"]["lint-unit"])
    assert "--kind bench --mesh 1" in lint_runs


def test_ci_lm_smoke_job():
    """The whole-model decode smoke: a bare-default lm serve session
    gated (incl. the model_verdict claim) against the committed
    schema-4 baseline.  Bare defaults are load-bearing: compare.py
    refuses joined keys whose rate/duration/SLO/batching knobs differ
    from the baseline's, so the serve command must carry no knobs."""
    job = _load("ci.yml")["jobs"]["lm-smoke"]
    runs = _run_text(job)
    assert "benchmarks.run serve --workload lm --config deepseek_7b" in runs
    assert "--out runs-ci-lm" in runs
    assert "benchmarks.compare runs runs-ci-lm" in runs
    assert "--kernels lm-deepseek-7b" in runs and "--kind serving" in runs
    # no traffic/batching knobs on the serve command (defaults must
    # match the committed baseline exactly)
    serve_line = next(line for line in runs.splitlines()
                      if "benchmarks.run serve --workload lm" in line)
    for knob in ("--rate", "--duration", "--max-batch", "--slo-ms",
                 "--seed", "--prompt-len", "--gen"):
        assert knob not in serve_line
    uploads = [s for s in job["steps"]
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and "runs-ci-lm" in uploads[0]["with"]["path"]


def test_ci_chaos_smoke_job():
    """The elastic-runtime chaos smoke: a 2-way bursty serve under the
    committed baseline's exact injected adversary, gated (incl. the
    elastic_integrity claim and the availability arm) against the
    committed schema-4 chaos baseline.  The spec and bare
    rate/duration are load-bearing: chaos_spec is a comparability
    knob, so compare.py refuses a drifted adversary."""
    job = _load("ci.yml")["jobs"]["chaos-smoke"]
    runs = _run_text(job)
    assert ("benchmarks.run serve --workload bursty --kernels scale "
            "--mesh 2 --chaos") in runs
    assert '"fail@0.6:1,resize@1.1:4,resize@1.6:2"' in runs
    assert "--out runs-ci-chaos" in runs
    assert "benchmarks.compare runs runs-ci-chaos" in runs
    assert "--kind serving --mesh 2" in runs
    # no traffic knobs on the serve command (defaults must match the
    # committed chaos baseline exactly)
    serve_line = next(line for line in runs.splitlines()
                      if "benchmarks.run serve" in line)
    for knob in ("--rate", "--duration", "--max-batch", "--slo-ms",
                 "--seed", "--size"):
        assert knob not in serve_line
    uploads = [s for s in job["steps"]
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and "runs-ci-chaos" in uploads[0]["with"]["path"]


def test_ci_trace_smoke_job():
    """The observability smoke: fresh Chrome-trace exports from both
    clocks — a --trace kernel sweep (wall spans + roofline counters)
    and a --trace-out chaos serve under the committed adversary
    (virtual spans) — validated by the repro.obs.trace CLI and
    uploaded as artifacts."""
    job = _load("ci.yml")["jobs"]["trace-smoke"]
    runs = _run_text(job)
    assert "--trace trace-sweep.json" in runs
    assert "--trace-out trace-chaos.json" in runs
    # the chaos timeline must replay the committed adversary
    assert '--chaos "fail@0.6:1,resize@1.1:4,resize@1.6:2"' in runs
    assert ("python -m repro.obs.trace trace-sweep.json "
            "trace-chaos.json") in runs
    uploads = [s for s in job["steps"]
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and uploads[0].get("if") == "always()"
    path = uploads[0]["with"]["path"]
    assert "trace-sweep.json" in path and "trace-chaos.json" in path


def test_ci_model_tier_named_step():
    """The decode-engine + verdict test modules are a named fast-lane
    step (failures findable from the job summary)."""
    runs = _run_text(_load("ci.yml")["jobs"]["lint-unit"])
    assert "tests/test_model_engine.py" in runs
    assert "tests/test_model_verdict.py" in runs


def test_pytest_tier_markers_declared():
    """The tier markers the CI -m filters select on must be declared
    in pyproject (an undeclared marker is a silent no-op filter)."""
    pyproject = pathlib.Path(__file__).resolve().parent.parent / \
        "pyproject.toml"
    text = pyproject.read_text()
    # text-level check (tomllib is 3.11+; the matrix floor is 3.10):
    # each tier must appear as a "<name>: ..." marker declaration
    assert "markers = [" in text
    for tier in ("unit", "model", "distributed", "property"):
        assert f'"{tier}: ' in text, f"marker {tier!r} not declared"


def test_nightly_covers_committed_mesh_widths():
    """The nightly bench gate runs with the default --mesh all, so its
    candidate sweep must reproduce every committed mesh width."""
    runs = _run_text(_load("nightly.yml")["jobs"]["sweep"])
    assert "benchmarks.run scale stencil --mesh 2" in runs
    assert "benchmarks.run scale --mesh 4" in runs


def test_nightly_schedule_and_artifacts():
    wf = _load("nightly.yml")
    on = _on(wf)
    crons = [s["cron"] for s in on["schedule"]]
    assert crons and all(len(c.split()) == 5 for c in crons)
    assert "workflow_dispatch" in on

    job = wf["jobs"]["sweep"]
    runs = _run_text(job)
    # full sweep + regression gate + serving sweep
    assert "benchmarks.run kernels --out runs-nightly" in runs
    assert "benchmarks.compare runs runs-nightly" in runs
    assert "benchmarks.run serve --out runs-serve-nightly" in runs
    assert "benchmarks.compare runs runs-serve-nightly" in runs
    assert "--kind serving --mesh 1" in runs
    assert "--tuned" not in runs
    # the chaos sweep replays the committed adversary and gates it
    assert "--chaos \"fail@0.6:1,resize@1.1:4,resize@1.6:2\"" in runs
    assert "benchmarks.compare runs runs-chaos-nightly" in runs
    assert "--kind serving --mesh 2" in runs
    uploads = [s for s in job["steps"]
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and uploads[0].get("if") == "always()"
    path = uploads[0]["with"]["path"]
    assert "compare-gate.txt" in path
    assert "runs-serve-nightly" in path and "serve-gate.txt" in path
    assert "runs-chaos-nightly" in path and "chaos-gate.txt" in path
