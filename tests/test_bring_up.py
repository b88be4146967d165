"""Bring-up guards: nothing on the served path hides the device, and nothing
that needs the chip is started behind a parent that already holds it.

CPU-cheap by construction — no test here builds a model: the compile-cache
function under both states of its environment variable, ``chip_smoke.py``
refusing a CPU before a model exists, the compile-exempt watchdog wait, and
the rule that no ``interpret=`` argument in the package is computed from the
backend.
"""

import ast
import os
import subprocess
import sys
import threading
import time

import jax
import pytest

from k_llms_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


# -- compile cache -----------------------------------------------------------

@pytest.fixture
def fresh_cache_config(monkeypatch):
    """configure_compile_cache() as a first call, with jax.config.update
    recorded instead of applied and no listener registered twice."""
    from jax import monitoring

    updates = []
    monkeypatch.setattr(compile_cache, "_configured", False)
    monkeypatch.setattr(compile_cache, "_cache_dir", None)
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    for name in (
        "register_scalar_listener",
        "register_event_duration_secs_listener",
        "register_event_listener",
    ):
        monkeypatch.setattr(monitoring, name, lambda cb: None)
    return updates


def test_compile_cache_env_var_wins_and_code_sets_nothing(monkeypatch, fresh_cache_config):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/some/dir")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.configure_compile_cache() == "/some/dir"
    assert fresh_cache_config == []
    assert compile_cache.compile_stats()["cache_dir"] == "/some/dir"


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, fresh_cache_config
):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    assert compile_cache.configure_compile_cache() == compile_cache.DEFAULT_CACHE_DIR
    assert fresh_cache_config == [
        ("jax_compilation_cache_dir", compile_cache.DEFAULT_CACHE_DIR)
    ]
    # Idempotent: the second call neither moves the path nor sets it again.
    assert compile_cache.configure_compile_cache() == compile_cache.DEFAULT_CACHE_DIR
    assert len(fresh_cache_config) == 1


def test_compile_cache_default_is_not_applied_on_the_cpu(monkeypatch, fresh_cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.configure_compile_cache() is None
    assert fresh_cache_config == []


def test_watchdog_wait_does_not_charge_compile_time():
    """A launch that spends 0.4 s "compiling" and 0.1 s running finishes
    inside a 0.25 s budget; the same launch with no compile phase does not."""

    def launch(tracker, done, compile_s):
        with tracker.active():
            if compile_s:
                compile_cache._on_phase_start("/jax/core/compile/jaxpr_trace_duration", 0.0)
                time.sleep(compile_s)
                compile_cache._on_phase_end("/jax/core/compile/jaxpr_trace_duration", compile_s)
            else:
                time.sleep(0.4)
            time.sleep(0.1)
        done.set()

    verdicts = {}
    for label, compile_s in (("compiling", 0.4), ("hung", 0.0)):
        tracker, done = compile_cache.CompileTracker(), threading.Event()
        t = threading.Thread(target=launch, args=(tracker, done, compile_s))
        t.start()
        verdicts[label] = compile_cache.wait_excluding_compile(done, 0.25, tracker, 60.0)
        t.join(timeout=5)
        assert not t.is_alive()
    assert verdicts == {"compiling": True, "hung": False}
    # The exemption is capped, so a compile that never returns is caught.
    tracker, done = compile_cache.CompileTracker(), threading.Event()
    with tracker.active():
        compile_cache._on_phase_start("/jax/core/compile/backend_compile_duration", 0.0)
        assert not compile_cache.wait_excluding_compile(done, 0.05, tracker, 0.1)


# -- chip_smoke.py -------------------------------------------------------------

def _run_smoke(script, **env):
    return subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=60,
        env={**os.environ, **env},
    )


def test_chip_smoke_refuses_cpu_before_building_a_model():
    t0 = time.monotonic()
    proc = _run_smoke(SMOKE, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line
    assert "JAX_PLATFORMS=cpu" in proc.stderr
    assert time.monotonic() - t0 < 20  # never got as far as a server


def test_chip_smoke_refuses_a_directory_without_the_package(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SMOKE, "rb").read())
    proc = _run_smoke(str(lone), JAX_PLATFORMS="")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_smoke_parent_imports_only_the_standard_library():
    """One process per chip: the parent must never initialise a backend."""
    tree = ast.parse(open(SMOKE).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= set(sys.stdlib_module_names), imported - set(sys.stdlib_module_names)


# -- device facts are read, not assumed --------------------------------------------

def test_hbm_size_is_read_from_an_accelerator_or_raises(monkeypatch):
    from k_llms_tpu.backends import tpu

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices", lambda: [Dev({"bytes_limit": 123})])
    assert tpu._detect_hbm_bytes() == 123
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(None)])
    with pytest.raises(RuntimeError, match="reports no memory limit"):
        tpu._detect_hbm_bytes()
    monkeypatch.undo()
    assert tpu._detect_hbm_bytes() == tpu.CPU_PLANNING_BYTES  # the CPU has no HBM


def test_dryrun_without_enough_devices_is_an_error_unless_explicitly_on_cpu(monkeypatch):
    import __graft_entry__ as graft

    def no_child(*a, **k):
        raise AssertionError("re-executed onto the CPU without being asked to")

    monkeypatch.setattr(graft.subprocess, "run", no_child)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        graft.dryrun_multichip(64)


def test_serving_entry_point_passes_quantization_and_model_parallel():
    from k_llms_tpu.serving.__main__ import _parse_args

    args = _parse_args(["--model", "qwen2-7b", "--quantization", "int8", "--model-parallel", "2"])
    assert (args.quantization, args.model_parallel) == ("int8", 2)
    assert _parse_args([]).quantization is None and _parse_args([]).model_parallel is None


# -- interpret mode is asked for by name ---------------------------------------------

def _python_sources():
    yield os.path.join(ROOT, "__graft_entry__.py")
    for dirpath, _, files in os.walk(os.path.join(ROOT, "k_llms_tpu")):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def test_no_interpret_argument_is_computed_from_the_backend():
    """``interpret=`` (and any local named ``interpret``) may depend on an
    explicit name such as "flash_interpret", never on what JAX runs on."""
    sniffers = {"default_backend", "platform", "devices", "local_devices", "device_kind"}
    offenders = []
    for path in _python_sources():
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            values = []
            if isinstance(node, ast.Call):
                values = [k.value for k in node.keywords if k.arg == "interpret"]
            elif isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "interpret" for t in node.targets
            ):
                values = [node.value]
            for value in values:
                names = {
                    n.attr if isinstance(n, ast.Attribute) else getattr(n, "id", None)
                    for n in ast.walk(value)
                }
                if names & sniffers:
                    offenders.append(f"{os.path.relpath(path, ROOT)}:{value.lineno}")
    assert not offenders, offenders
