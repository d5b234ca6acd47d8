"""Public-API documentation gate for the paper-facing modules.

Every public symbol of ``repro.core.dispatch``, ``repro.kernels.registry``,
``repro.report``, and the full ``repro.serving`` / ``repro.sharding`` /
``repro.runtime`` (checkpoint + elastic) surfaces must carry a
docstring, and the curated paper-facing callables
must cite the paper section or equation they implement ("§n" or
"Eq. n") so the code stays navigable against PAPER.md."""
import importlib
import inspect

import pytest

MODULES = (
    "repro.core.dispatch",
    "repro.kernels.registry",
    "repro.report",
    "repro.report.records",
    "repro.report.claims",
    "repro.report.render",
    "repro.serving",
    "repro.serving.loadgen",
    "repro.serving.requests",
    "repro.serving.scheduler",
    "repro.serving.batcher",
    "repro.serving.lm",
    "repro.serving.metrics",
    "repro.serving.session",
    "repro.serving.slo",
    "repro.serving.elastic",
    "repro.serving.router",
    "repro.runtime.checkpoint",
    "repro.runtime.elastic",
    "repro.sharding",
    "repro.sharding.plan",
    "repro.sharding.executor",
    "repro.sharding.rules",
    "repro.sharding.collective_matmul",
    "repro.launch.mesh",
    "repro.launch.compile_cache",
)

# (module, qualname) pairs whose docstrings must cite the paper.
PAPER_CITED = (
    ("repro.core.dispatch", "Dispatcher"),
    ("repro.core.dispatch", "Dispatcher.advise"),
    ("repro.core.dispatch", "Dispatcher.resolve"),
    ("repro.core.dispatch", "default_cache_key"),
    ("repro.core.dispatch", "elementwise_call"),
    ("repro.core.dispatch", "normalize_engine"),
    ("repro.kernels.registry", "EngineOp"),
    ("repro.kernels.registry", "EngineOp.advice"),
    ("repro.kernels.registry", "register"),
    ("repro.report.records", "BenchRecord"),
    ("repro.report.records", "ServingRecord"),
    ("repro.report.records", "load_file"),
    ("repro.report.claims", "ceiling_bound"),
    ("repro.report.claims", "check_record"),
    ("repro.report.claims", "check_serving_record"),
    ("repro.report.render", "render_report"),
    ("repro.report.render", "write_report"),
    ("repro.serving.scheduler", "ContinuousBatchingScheduler"),
    ("repro.serving.batcher", "KernelBatchExecutor"),
    ("repro.serving.metrics", "serving_record"),
    ("repro.serving.session", "run_session"),
    ("repro.serving.router", "SLORouter"),
    ("repro.sharding.plan", "ShardSpec"),
    ("repro.sharding.plan", "ShardPlan"),
    ("repro.sharding.plan", "plan_for"),
    ("repro.sharding.plan", "spec_for"),
    ("repro.sharding.plan", "traffic"),
    ("repro.sharding.executor", "ShardedExecutor"),
)


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [n for n in vars(mod) if not n.startswith("_")]


def _doc(obj) -> str:
    return (inspect.getdoc(obj) or "").strip()


@pytest.mark.parametrize("modname", MODULES)
def test_module_docstring(modname):
    assert _doc(importlib.import_module(modname)), modname


@pytest.mark.parametrize("modname", MODULES)
def test_public_symbols_have_docstrings(modname):
    mod = importlib.import_module(modname)
    undocumented = []
    for name in _public_names(mod):
        obj = getattr(mod, name)
        if not (inspect.isclass(obj) or inspect.isroutine(obj)):
            continue  # constants, singletons
        if not _doc(obj):
            undocumented.append(name)
        if inspect.isclass(obj):
            for mname, member in vars(obj).items():
                if mname.startswith("_") and mname != "__call__":
                    continue
                if isinstance(member, property):
                    member = member.fget
                if inspect.isroutine(member) and not _doc(member):
                    undocumented.append(f"{name}.{mname}")
    assert not undocumented, (
        f"{modname}: public API missing docstrings: {undocumented}")


@pytest.mark.parametrize("modname,qualname", PAPER_CITED)
def test_paper_facing_api_cites_paper(modname, qualname):
    obj = importlib.import_module(modname)
    for part in qualname.split("."):
        obj = getattr(obj, part)
    doc = _doc(obj)
    assert "§" in doc or "Eq." in doc, (
        f"{modname}.{qualname} must cite its paper section "
        f"('§n' or 'Eq. n'); docstring: {doc!r}")
