"""Test configuration: force an 8-device virtual CPU platform before JAX
backends initialize.

The reference (k-LLMs) has no hermetic test story (SURVEY.md §4); ours runs the
whole framework — including the "distributed" decode path — on a simulated
8-device CPU mesh so no TPU hardware is needed for CI. The platform is pinned
through jax.config as well as the tier-1 command's JAX_PLATFORMS=cpu, so a
bare ``pytest`` on a machine with a chip still runs on the host.
"""

import importlib
import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def benchmark_module(name):
    """A module of ``benchmark/`` (a plain reference, ``run``, ``reduce_trace``),
    imported from there. The directory is on ``sys.path`` only while the
    module loads, so none of its names shadows another module."""
    where = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, where)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(where)


# -- shared engines ----------------------------------------------------------
# Engine construction dominates suite wall time: every LocalEngine owns its
# own jit caches, so two module fixtures building "the same" engine compile
# every prefill/decode program twice. This session-scoped factory hands out
# ONE engine per construction key — identical engines across test_w4 /
# test_sp_decode / test_tpu_backend / test_speculative share compiles.
#
# Engines are STATEFUL (prefix cache, spec_stats, jit caches): tests that
# assert on those counters must reset them or build a private engine.
_PARAMS_CACHE = {}
_ENGINE_CACHE = {}


def shared_params(config, param_key=0):
    """init_params once per (config, seed) — configs are hashable."""
    key = (config, param_key)
    params = _PARAMS_CACHE.get(key)
    if params is None:
        from k_llms_tpu.models import init_params

        params = init_params(config, jax.random.key(param_key))
        _PARAMS_CACHE[key] = params
    return params


def shared_engine(model="tiny", *, param_key=0, mesh_shape=None, **kwargs):
    """One LocalEngine per (model-or-config, params seed, mesh shape, engine
    knobs) for the whole session. ``model``: registered name or ModelConfig;
    ``mesh_shape``: (data, model) for make_mesh, None = use_mesh=False.
    Extra kwargs go to LocalEngine verbatim (and join the cache key)."""
    from k_llms_tpu.models import get_config

    config = get_config(model) if isinstance(model, str) else model
    key = (config, param_key, mesh_shape, tuple(sorted(kwargs.items())))
    eng = _ENGINE_CACHE.get(key)
    if eng is None:
        from k_llms_tpu.engine.engine import LocalEngine

        # Always hand the engine the shared full-precision tree (it quantizes
        # passed-in params itself): a meshed engine's own param_seed init is
        # sharded and draws DIFFERENT values than the host-side init, which
        # would break solo-vs-mesh bit-equality tests.
        params = shared_params(config, param_key)
        if mesh_shape is None:
            eng = LocalEngine(
                config, params=params, use_mesh=False, param_seed=param_key,
                **kwargs,
            )
        else:
            from k_llms_tpu.parallel.mesh import make_mesh

            eng = LocalEngine(
                config, params=params, mesh=make_mesh(*mesh_shape),
                param_seed=param_key, **kwargs,
            )
        _ENGINE_CACHE[key] = eng
    return eng


_LOOP_CACHE = {}


def shared_loop(model, *, kv_layout="paged", kv_page_size=16, **kwargs):
    """One ContinuousDecodeLoop per (shared engine, loop knobs) for the
    session: a loop's step and chunk programs are closures of the instance, so
    a test that builds its own compiles them again. Extra kwargs go to the
    loop verbatim. A test that reads the loop's counters takes them before and
    after its request; a test that needs a fresh loop, a hung step, a
    rebuild or a patched kernel builds its own."""
    engine = shared_engine(model, kv_layout=kv_layout, kv_page_size=kv_page_size)
    key = (id(engine), tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                                    for k, v in kwargs.items())))
    loop = _LOOP_CACHE.get(key)
    if loop is None:
        from k_llms_tpu.engine.continuous import ContinuousDecodeLoop

        loop = _LOOP_CACHE[key] = ContinuousDecodeLoop(engine, **kwargs)
    return loop


def stats_grown(before, after):
    """What a loop's counters (``loop.stats``, the page pool's among them)
    gained between two reads; other entries as the second read has them."""
    return {k: stats_grown(before.get(k, {}), v) if isinstance(v, dict)
            else v - before.get(k, 0) if type(v) in (int, float) else v
            for k, v in after.items()}


def pytest_sessionfinish(session, exitstatus):
    for loop in _LOOP_CACHE.values():
        loop.stop()


# -- memory mappings ---------------------------------------------------------
# Every compiled XLA:CPU program holds memory mappings of its own, and a test
# process that compiles many (an xdist worker runs a dozen files) reaches the
# kernel's per-process limit (vm.max_map_count, 65,530 by default): the next
# compile then aborts or segfaults the process, wherever it happens to be. So
# when a test file ends with the process past half that limit, JAX's compiled
# programs are dropped; engines compile theirs again on next use.
_MAPS_HIGH = 32768


def pytest_runtest_teardown(item, nextitem):
    if nextitem is not None and nextitem.module is item.module:
        return
    try:
        with open("/proc/self/maps") as f:
            maps = sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count
        return
    if maps > _MAPS_HIGH:
        import gc

        jax.clear_caches()
        gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "mesh: requires the 8-device virtual CPU mesh (conftest sets it up; "
        "a caller-preset XLA_FLAGS without the device-count flag breaks it)",
    )
    config.addinivalue_line(
        "markers",
        "duration_budget(seconds): declared expected runtime; budgets over "
        "30s require the `slow` tag (enforced at collection by "
        "tests/_duration_guard.py)",
    )


def pytest_collection_modifyitems(config, items):
    """Tag every mesh-environment-gated test with the explicit ``mesh`` marker
    (VERDICT r2 weak #7): `pytest -m mesh` runs exactly the multi-device
    suites, and test_environment.py fails loudly when they would all silently
    skip because the virtual mesh is missing."""
    import pytest

    for item in items:
        for m in item.iter_markers("skipif"):
            reason = str(m.kwargs.get("reason", "")) + "".join(
                str(a) for a in m.args if isinstance(a, str)
            )
            if "8-device CPU mesh" in reason or "mesh" in reason.lower():
                item.add_marker(pytest.mark.mesh)
                break

    # Duration-budget guard: a test declaring a budget over the tier-1
    # threshold without a `slow` tag fails COLLECTION (deterministic, instant)
    # instead of flaking the 870 s tier-1 timeout at runtime.
    from _duration_guard import enforce

    enforce(items)
